"""Output-space tile calculus (paper Eq. 5 + legality constraints).

The reverse-loop algorithm tiles the *output* space into disjoint
``T_OH x T_OW`` blocks (no overlapping-sum problem), and the input tile
required per output tile has the *constant* extent of Eq. 5:

    T_IH = ceil(T_OH / S) + ceil(K / S)                       (Eq. 5)

independent of the tile position — the property that makes the FPGA CU
workloads uniform, and that makes our Pallas BlockSpecs static.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

from .offsets import PhasePlan, make_phase_plan


def out_size(in_size: int, kernel: int, stride: int, padding: int) -> int:
    """Transposed-conv output extent (PyTorch ConvTranspose2d convention)."""
    return (in_size - 1) * stride + kernel - 2 * padding


def in_size_for(out_size_: int, kernel: int, stride: int, padding: int) -> int:
    n = out_size_ - kernel + 2 * padding
    assert n % stride == 0, "inconsistent deconv geometry"
    return n // stride + 1


def input_tile_extent(t_oh: int, kernel: int, stride: int) -> int:
    """Paper Eq. 5 (an upper bound on the exact extent; see tests)."""
    return math.ceil(t_oh / stride) + math.ceil(kernel / stride)


def exact_input_extent(
    t_oh: int, kernel: int, stride: int, padding: int
) -> int:
    """Exact max-over-tiles input extent max(i)-min(i)+1 for an S-aligned tile
    of T_OH output pixels.  Property-tested to be <= Eq. 5's bound."""
    plan = make_phase_plan(kernel, stride, padding)
    # rows accessed for tile rows [0, T_OH): i = t + delta, t in [0, ceil(T_OH/S))
    lo = plan.delta_min
    hi = (t_oh - 1) // stride + plan.delta_max
    return hi - lo + 1


@dataclasses.dataclass(frozen=True)
class HaloTile:
    """Eq. 5 input-tile geometry for one spatial dim of the Pallas kernel.

    An S-aligned output tile of ``t_out`` pixels starting at output row
    ``j * t_out`` reads the *constant-extent* input window

        rows [ j * (t_out // S) + base,  j * (t_out // S) + base + extent )

    of the host-padded input — ``extent = t_out/S + delta_max - delta_min``
    (the exact form of the paper's Eq. 5 bound) and ``base >= 0`` because
    the host pads ``left_halo`` rows on the left.  Consecutive windows
    overlap by ``extent - t_out/S`` halo rows; the kernel's per-tap slices
    inside the window are *static*: tap displacement ``d`` lives at local
    row ``d + local_zero``.  An ``align``-ed window (`halo_tile`) starts
    at an ``align`` multiple and spans a whole number of ``align`` rows.
    """

    t_out: int       # output tile extent (multiple of S)
    stride: int
    extent: int      # input window extent T_I (rows streamed per tile)
    base: int        # element offset of tile j's window: j*(t_out/S) + base
    local_zero: int  # local row of displacement delta=0

    @property
    def step(self) -> int:
        """Window start advance per output tile (t_out / S input rows)."""
        return self.t_out // self.stride

    @property
    def overlap(self) -> int:
        """Halo rows shared by consecutive windows."""
        return self.extent - self.step

    def local_offset(self, delta: int) -> int:
        """Static in-window row of a tap with input displacement ``delta``."""
        return delta + self.local_zero

    def min_padded_extent(self, n_tiles: int) -> int:
        """Smallest padded input extent covering all n_tiles windows."""
        return (n_tiles - 1) * self.step + self.base + self.extent


def halo_tile(t_out: int, kernel: int, stride: int, padding: int,
              align: int = 1) -> HaloTile:
    """Input-window geometry for an S-aligned output tile (paper Eq. 5).

    The window extent equals ``exact_input_extent`` — the max-over-tiles
    input span — so the Pallas BlockSpec streams exactly the rows the tile
    touches (plus nothing), which is what drops per-tile HBM traffic from
    O(padded image) to O(T_I).

    ``align`` is for the input's second-minor (W) dim, which Mosaic lays
    out in ``SUBLANE``-row tiles: a DMA window there must start on a tile
    boundary.  The window's base rounds down to an ``align`` multiple (the
    dropped rows shift ``local_zero``) and its extent rounds up to whole
    tiles; the caller keeps ``t_out / S`` an ``align`` multiple whenever
    more than one window exists.
    """
    assert t_out % stride == 0, "tiles must be stride-aligned"
    plan = make_phase_plan(kernel, stride, padding)
    step = t_out // stride
    extent = step + plan.delta_max - plan.delta_min
    # host pads left_halo = max(0, -delta_min) rows; window j then starts at
    # j*step + (left_halo + delta_min) = j*step + max(0, delta_min) >= 0.
    base = plan.left_halo + plan.delta_min
    shift = base % align
    return HaloTile(
        t_out=t_out,
        stride=stride,
        extent=_round_up(extent + shift, align),
        base=base - shift,
        local_zero=shift - plan.delta_min,
    )


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# Mosaic lays the last two dims of every VMEM block out in (sublane, lane)
# tiles of (SUBLANE * 4 // itemsize, LANE) elements: 8x128 for 32-bit data,
# 32x128 for int8.
SUBLANE = 8
LANE = 128


def tap_fold_group(k: int, t_co: int) -> int:
    """Taps of a ``k`` x ``k`` kernel whose products one lane-wide dot of
    the dense kernel yields at once for an output-channel tile of
    ``t_co``: as many as fit a lane (``LANE // t_co``), at most all
    ``k * k``.  1 is the per-tap form, which a tile a lane wide keeps:
    there a fold saves no MXU pass."""
    return max(1, min(k * k, LANE // t_co))


def tiled_bytes(shape: Tuple[int, ...], itemsize: int) -> int:
    """VMEM bytes of a block once its last two dims are padded to whole
    (sublane, lane) tiles — an 8-channel f32 block occupies 128 lanes."""
    *lead, sub, lane = shape
    return (math.prod(lead) * _round_up(sub, SUBLANE * 4 // itemsize)
            * _round_up(lane, LANE) * itemsize)


def kernel_vmem_bytes(
    geom: DeconvGeometry,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    dtype_bytes: int = 4,
    t_n: int = 1,
    out_dtype_bytes: Optional[int] = None,
) -> int:
    """VMEM footprint of the halo-streaming Pallas kernel, as Mosaic lays
    it out.

    Every block is counted in whole (sublane, lane) tiles (`tiled_bytes`).
    Input, weight, epilogue-vector and output blocks are double-buffered
    by the Pallas pipeline (x2); the 4-byte accumulator scratch (f32 for
    the dense/sparse kernels, int32 for the int8 kernel) is single, and
    one phase's partial sum plus one tap slice live as in-kernel values.
    A float layer that `tap_fold_group` folds also holds one slab's dot
    of the whole window, ``(T_N * T_IH * T_IW, LANE)`` f32 scratch, and
    slices its taps' blocks, not the input's: it counts the larger of the
    two forms' temporaries, as the sparse kernel, which shares this model
    and keeps the per-tap form, may take the same tile.
    ``t_n`` is the batch tile: each grid program owns ``t_n`` images' halo
    windows / output blocks (the weight slab is batch-stationary).
    ``dtype_bytes`` is the streamed element width (1 for the int8 kernel);
    ``out_dtype_bytes`` overrides the output block's width when it differs
    from the inputs' (an int8 layer whose epilogue emits f32)."""
    k, s = geom.kernel, geom.stride
    ht_h = halo_tile(t_oh, k, s, geom.padding)
    ht_w = halo_tile(t_ow, k, s, geom.padding, align=SUBLANE)
    out_b = dtype_bytes if out_dtype_bytes is None else out_dtype_bytes
    x_bytes = tiled_bytes((t_n, ht_h.extent, ht_w.extent, t_ci), dtype_bytes)
    w_bytes = tiled_bytes((k, k, t_ci, t_co), dtype_bytes)
    # epilogue vectors stream as f32 (1, T_CO) rows: bias for the float
    # kernels, bias AND the per-channel requant scale for the int8 kernel
    v_bytes = (2 if dtype_bytes == 1 else 1) * tiled_bytes((1, t_co), 4)
    y_bytes = tiled_bytes((t_n, t_oh, t_ow, t_co), out_b)
    acc_bytes = tiled_bytes((t_n, t_oh, t_ow, t_co), 4)
    rows = t_n * (t_oh // s) * (t_ow // s)
    tmp_bytes = (tiled_bytes((rows, t_co), 4)
                 + tiled_bytes((rows, t_ci), dtype_bytes))
    if dtype_bytes != 1 and tap_fold_group(k, t_co) > 1:
        window = t_n * ht_h.extent * ht_w.extent
        tmp_bytes = max(tmp_bytes, tiled_bytes((window, LANE), 4)
                        + 2 * tiled_bytes((rows, t_co), 4))
    return (2 * (x_bytes + w_bytes + v_bytes + y_bytes) + acc_bytes
            + tmp_bytes)


@dataclasses.dataclass(frozen=True)
class DeconvGeometry:
    """Static geometry of one deconv layer."""

    in_h: int
    in_w: int
    c_in: int
    c_out: int
    kernel: int
    stride: int
    padding: int

    @property
    def out_h(self) -> int:
        return out_size(self.in_h, self.kernel, self.stride, self.padding)

    @property
    def out_w(self) -> int:
        return out_size(self.in_w, self.kernel, self.stride, self.padding)

    @property
    def macs(self) -> int:
        """Multiply-accumulates for the full layer (per batch element).
        Every (input pixel, tap, c_in, c_out) combination is one MAC."""
        return self.in_h * self.in_w * self.kernel * self.kernel * self.c_in * self.c_out

    @property
    def ops(self) -> int:
        """GOps convention of the paper: 2 ops per MAC."""
        return 2 * self.macs

    def phase_plan(self) -> PhasePlan:
        return make_phase_plan(self.kernel, self.stride, self.padding)

    def halo_padding(self) -> Tuple[int, int]:
        """(pad_left, pad_right) applied to the input spatial dims so that
        every tap access of every S-aligned output tile is in bounds
        (enhancement (3): all address arithmetic is resolved ahead of the
        kernel; the device performs only static in-bounds slices)."""
        plan = self.phase_plan()
        pad_l = plan.left_halo
        # Worst-case right access for the last (possibly ragged) tile:
        # o = out_h - 1 -> t_max = (out_h - 1) // S within its phase, plus halo.
        i_max = (self.out_h - 1) // self.stride + plan.delta_max
        pad_r = max(0, i_max - (self.in_h - 1))
        return pad_l, pad_r


@dataclasses.dataclass(frozen=True)
class ConvGeometry(DeconvGeometry):
    """Static geometry of one strided forward-convolution layer (an
    encoder layer of an image-to-image tower): the same fields, with the
    forward convolution's output extent ``(in + 2P - K) // S + 1`` and one
    MAC per (output pixel, tap, c_in, c_out).  It has no phase plan: the
    deconvolution kernels never run it."""

    @property
    def out_h(self) -> int:
        return (self.in_h + 2 * self.padding - self.kernel) // self.stride + 1

    @property
    def out_w(self) -> int:
        return (self.in_w + 2 * self.padding - self.kernel) // self.stride + 1

    @property
    def macs(self) -> int:
        return (self.out_h * self.out_w * self.kernel * self.kernel
                * self.c_in * self.c_out)

    def phase_plan(self) -> PhasePlan:
        raise TypeError("a forward convolution has no deconvolution "
                        "phase plan")

    def halo_padding(self) -> Tuple[int, int]:
        raise TypeError("a forward convolution has no deconvolution halo")


@dataclasses.dataclass(frozen=True)
class DeconvTraffic:
    """Modeled HBM traffic of the halo-streaming kernel for one layer
    (per batch element).  ``in_bytes_per_tile`` is the Eq. 5 window — a
    constant per tile, independent of image size (the paper's point).
    Bytes only; CTC / attainable throughput live in `dse.tile_attainable`.
    """

    n_tiles: int              # spatial x C_out output tiles
    n_ci_steps: int           # C_in grid steps per output tile
    in_bytes_per_tile: int    # halo window bytes per (tile, ci step)
    w_bytes_per_tile: int     # weight slab bytes per (tile, ci step)
    out_bytes_per_tile: int   # one-shot output block bytes
    total_bytes: int


def deconv_traffic(
    geom: DeconvGeometry,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    dtype_bytes: int = 4,
) -> DeconvTraffic:
    """HBM bytes moved by the halo-streaming kernel (per batch element).

    Per output tile the CI grid re-streams one Eq. 5 input window and one
    weight slab per CI step; the output block is written once.  This is the
    modeled side of the modeled-vs-measured accounting in
    benchmarks/bench_deconv.py."""
    ht_h = halo_tile(t_oh, geom.kernel, geom.stride, geom.padding)
    ht_w = halo_tile(t_ow, geom.kernel, geom.stride, geom.padding)
    n_h = -(-geom.out_h // t_oh)
    n_w = -(-geom.out_w // t_ow)
    n_co = -(-geom.c_out // t_co)
    n_ci = -(-geom.c_in // t_ci)
    in_b = ht_h.extent * ht_w.extent * t_ci * dtype_bytes
    w_b = geom.kernel * geom.kernel * t_ci * t_co * dtype_bytes
    out_b = t_oh * t_ow * t_co * dtype_bytes
    n_tiles = n_h * n_w * n_co
    total = n_tiles * (n_ci * (in_b + w_b) + out_b)
    return DeconvTraffic(
        n_tiles=n_tiles,
        n_ci_steps=n_ci,
        in_bytes_per_tile=in_b,
        w_bytes_per_tile=w_b,
        out_bytes_per_tile=out_b,
        total_bytes=total,
    )


def deconv_traffic_batched(
    geom: DeconvGeometry,
    batch: int,
    t_n: int,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    dtype_bytes: int = 4,
    out_dtype_bytes: Optional[int] = None,
) -> DeconvTraffic:
    """HBM bytes moved for a *batch* under the batch-fused kernel.

    The batch dimension is tiled by ``t_n`` (batch folded into the MXU row
    dimension): each grid program streams ``t_n`` halo windows but only ONE
    weight slab per CI step, so weight traffic per image falls by ``t_n`` —
    the spatio-temporal amortization that makes the batched path win on the
    fat-channel early layers.  ``dtype_bytes`` is the streamed element
    width — 1 on the int8 path, where the quartered stream is half the
    paper's low-precision advantage — and ``out_dtype_bytes`` overrides
    the written block's width when the epilogue changes precision."""
    ht_h = halo_tile(t_oh, geom.kernel, geom.stride, geom.padding)
    ht_w = halo_tile(t_ow, geom.kernel, geom.stride, geom.padding)
    o_bytes = dtype_bytes if out_dtype_bytes is None else out_dtype_bytes
    n_n = -(-batch // t_n)
    n_h = -(-geom.out_h // t_oh)
    n_w = -(-geom.out_w // t_ow)
    n_co = -(-geom.c_out // t_co)
    n_ci = -(-geom.c_in // t_ci)
    in_b = t_n * ht_h.extent * ht_w.extent * t_ci * dtype_bytes
    w_b = geom.kernel * geom.kernel * t_ci * t_co * dtype_bytes
    out_b = t_n * t_oh * t_ow * t_co * o_bytes
    n_tiles = n_n * n_h * n_w * n_co
    total = n_tiles * (n_ci * (in_b + w_b) + out_b)
    return DeconvTraffic(
        n_tiles=n_tiles,
        n_ci_steps=n_ci,
        in_bytes_per_tile=in_b,
        w_bytes_per_tile=w_b,
        out_bytes_per_tile=out_b,
        total_bytes=total,
    )


def full_image_traffic(
    geom: DeconvGeometry,
    t_oh: int,
    t_ow: int,
    t_ci: int,
    t_co: int,
    dtype_bytes: int = 4,
) -> DeconvTraffic:
    """HBM traffic of the pre-halo pipeline (every grid program re-streamed
    the whole padded input per CI step) — the baseline the tentpole kills.
    Same structure as `deconv_traffic`; only ``in_bytes_per_tile`` differs
    (the whole padded image instead of the Eq. 5 window)."""
    pad_l, pad_r = geom.halo_padding()
    ihp = geom.in_h + pad_l + pad_r
    iwp = geom.in_w + pad_l + pad_r
    n_h = -(-geom.out_h // t_oh)
    n_w = -(-geom.out_w // t_ow)
    n_co = -(-geom.c_out // t_co)
    n_ci = -(-geom.c_in // t_ci)
    in_b = ihp * iwp * t_ci * dtype_bytes
    w_b = geom.kernel * geom.kernel * t_ci * t_co * dtype_bytes
    out_b = t_oh * t_ow * t_co * dtype_bytes
    n_tiles = n_h * n_w * n_co
    return DeconvTraffic(
        n_tiles=n_tiles,
        n_ci_steps=n_ci,
        in_bytes_per_tile=in_b,
        w_bytes_per_tile=w_b,
        out_bytes_per_tile=out_b,
        total_bytes=n_tiles * (n_ci * (in_b + w_b) + out_b),
    )


def legal_tile_factors(
    geom: DeconvGeometry,
    vmem_budget_bytes: int = 12 * 1024 * 1024,
    dtype_bytes: int = 4,
    co_tile: int = 128,
    model: str = "full_spatial",
) -> List[int]:
    """Enumerate legal square output tiling factors T_OH = T_OW (the paper
    explores square tiles).  Legality (the paper's Fig. 5 'legal solutions'):

    * S | T_OH       — tiles are stride-aligned so the phase structure is
                        identical for every tile (uniform CU workloads);
    * on-chip fit    — input block + weight block + output block + f32
                        accumulator fit the budget (VMEM / BRAM).

    `model`: "full_spatial" budgets our Pallas kernel (whole input spatial
    resident per C_in tile); "eq5" budgets the paper's FPGA dataflow (an
    Eq.-5 T_IH x T_IW input tile per output tile)."""
    out: List[int] = []
    s = geom.stride
    for t in range(s, geom.out_h + s, s):
        if t % s:
            continue
        t_oh = min(t, geom.out_h)
        footprint = _vmem_footprint(geom, t_oh, co_tile, dtype_bytes, model)
        if footprint <= vmem_budget_bytes:
            out.append(t)
        if t >= geom.out_h:
            break
    return sorted(set(out))


def _vmem_footprint(
    geom: DeconvGeometry, t_oh: int, co_tile: int, dtype_bytes: int,
    model: str = "full_spatial",
) -> int:
    co_t = min(co_tile, geom.c_out)
    if model == "eq5":
        # the FPGA dataflow streams Eq.-5 input tiles AND input-channel
        # blocks (Algorithm 1's i_c loop) through BRAM
        t_ih = input_tile_extent(t_oh, geom.kernel, geom.stride)
        in_spatial = t_ih * t_ih
        ci_t = min(32, geom.c_in)
    else:
        pad_l, pad_r = geom.halo_padding()
        in_spatial = ((geom.in_h + pad_l + pad_r)
                      * (geom.in_w + pad_l + pad_r))
        ci_t = geom.c_in
    x_bytes = in_spatial * ci_t * dtype_bytes
    w_bytes = geom.kernel * geom.kernel * ci_t * co_t * dtype_bytes
    y_bytes = t_oh * t_oh * co_t * dtype_bytes
    acc_bytes = t_oh * t_oh * co_t * 4  # f32 accumulator scratch
    return x_bytes + w_bytes + y_bytes + acc_bytes


def vmem_footprint(geom: DeconvGeometry, t_oh: int, co_tile: int = 128,
                   dtype_bytes: int = 4, model: str = "full_spatial") -> int:
    return _vmem_footprint(geom, t_oh, co_tile, dtype_bytes, model)
