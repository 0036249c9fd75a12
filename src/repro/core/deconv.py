"""The paper's reverse-loop deconvolution algorithm.

Three artifacts live here:

* ``deconv2d_algorithm1_numpy`` — a literal, instrumented transcription of the
  paper's Algorithm 1 (reverse loop over the output space, precomputed Eq. 3
  offsets, optional zero-skipping).  Used as the faithful-baseline oracle and
  to count executed MACs for the sparsity study (Fig. 6).
* ``deconv2d_reverse_loop`` — the TPU-native pure-JAX formulation: the Eq. 3
  offsets are folded into a trace-time *phase decomposition* so the device
  executes only static slices + channel matmuls (MXU-friendly), and the output
  is assembled with one pixel-shuffle.  This is the algorithm the Pallas
  kernel (kernels/deconv2d) implements per-tile.
* ``deconv2d_zero_insertion`` — the conventional zero-insertion formulation
  (what [23], [24], [22] build on, and what cuDNN/XLA execute): the paper's
  comparison baseline.

All take NHWC activations and (K, K, C_in, C_out) weights, with the
PyTorch-style geometry  O = (I-1)*S + K - 2P.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .offsets import make_phase_plan, offset_table
from .tiling import out_size

# leaky_relu's slope for negative inputs (the pix2pix encoders'): shared by
# the model's layers and the kernels' fused epilogue
LEAKY_SLOPE = 0.2


# ---------------------------------------------------------------------------
# Literal Algorithm 1 (numpy, instrumented)
# ---------------------------------------------------------------------------
def deconv2d_algorithm1_numpy(
    x: np.ndarray,
    w: np.ndarray,
    b: Optional[np.ndarray],
    stride: int,
    padding: int,
    t_oh: Optional[int] = None,
    t_ow: Optional[int] = None,
    zero_skip: bool = False,
) -> Tuple[np.ndarray, int]:
    """Paper Algorithm 1, per output tile, with Eq. 3 offsets precomputed.

    x: (IH, IW, CI);  w: (K, K, CI, CO);  returns (y (OH, OW, CO), macs).
    ``zero_skip`` reproduces the conditional-execution paradigm: weights equal
    to zero are skipped and the returned MAC count drops accordingly.
    """
    ih, iw, ci = x.shape
    k = w.shape[0]
    oh = out_size(ih, k, stride, padding)
    ow = out_size(iw, k, stride, padding)
    t_oh = t_oh or oh
    t_ow = t_ow or ow
    f = offset_table(k, stride, padding)  # enhancement (1): 2K modulo ops total
    y = np.zeros((oh, ow, w.shape[3]), dtype=np.float64)
    if b is not None:
        y += b  # initializeToBias()
    macs = 0
    # spatially-parallel CU workloads: disjoint output tiles
    for base_h in range(0, oh, t_oh):
        for base_w in range(0, ow, t_ow):
            # enhancement (2): weight loops outermost (loop interchange)
            for kh in range(k):
                for kw in range(k):
                    fh, fw = int(f[kh]), int(f[kw])
                    for oh_hat in range(0, t_oh, stride):
                        for ow_hat in range(0, t_ow, stride):
                            o_h = base_h + oh_hat + fh
                            o_w = base_w + ow_hat + fw
                            if o_h >= oh or o_w >= ow:
                                continue
                            i_h, rh = divmod(o_h + padding - kh, stride)
                            i_w, rw = divmod(o_w + padding - kw, stride)
                            assert rh == 0 and rw == 0, "offset math broken"
                            if not (0 <= i_h < ih and 0 <= i_w < iw):
                                continue
                            wv = w[kh, kw]  # (CI, CO)
                            if zero_skip:
                                nz = wv != 0.0
                                y[o_h, o_w] += x[i_h, i_w] @ (wv * nz)
                                macs += int(nz.sum())
                            else:
                                y[o_h, o_w] += x[i_h, i_w] @ wv
                                macs += wv.size
    return y.astype(x.dtype), macs


# ---------------------------------------------------------------------------
# TPU-native phase-decomposed reverse loop (pure JAX)
# ---------------------------------------------------------------------------
def _phase_pads(n_h: int, n_w: int, ih: int, iw: int, plan) -> Tuple[int, int, int, int]:
    pad_l = plan.left_halo
    pad_rh = max(0, (n_h - 1 + plan.delta_max) - (ih - 1))
    pad_rw = max(0, (n_w - 1 + plan.delta_max) - (iw - 1))
    return pad_l, pad_rh, pad_l, pad_rw


def deconv2d_reverse_loop(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    stride: int,
    padding: int,
    accum_dtype=jnp.float32,
) -> jax.Array:
    """Reverse-loop deconvolution with trace-time phase decomposition.

    Per output phase (ph, pw) and contributing tap (kh, kw) the contribution
    is a shifted slice of x contracted with w[kh, kw] — a channel matmul that
    maps onto the MXU.  Output is assembled with a single pixel shuffle
    (disjoint one-shot writes: enhancement (2)/(3)).
    """
    n, ih, iw, ci = x.shape
    k = w.shape[0]
    s = stride
    oh = out_size(ih, k, s, padding)
    ow = out_size(iw, k, s, padding)
    plan = make_phase_plan(k, s, padding)
    n_h = -(-oh // s)  # ceil: padded phase grid
    n_w = -(-ow // s)
    pl_, prh, pt, prw = _phase_pads(n_h, n_w, ih, iw, plan)
    xp = jnp.pad(x, ((0, 0), (pl_, prh), (pt, prw), (0, 0)))

    # (S, S) grid of phase accumulators, each (N, n_h, n_w, CO)
    co = w.shape[3]
    rows = []
    for ph in range(s):
        cols = []
        for pw in range(s):
            acc = jnp.zeros((n, n_h, n_w, co), dtype=accum_dtype)
            for kh, dh in plan.taps[ph]:
                for kw, dw in plan.taps[pw]:
                    xs = jax.lax.dynamic_slice(
                        xp,
                        (0, pl_ + dh, pt + dw, 0),
                        (n, n_h, n_w, ci),
                    )
                    acc = acc + jnp.einsum(
                        "nhwc,cd->nhwd",
                        xs,
                        w[kh, kw],
                        preferred_element_type=accum_dtype,
                    )
            cols.append(acc)
        rows.append(jnp.stack(cols, axis=0))  # (S_w, N, n_h, n_w, CO)
    y = jnp.stack(rows, axis=0)  # (S_h, S_w, N, n_h, n_w, CO)
    # pixel shuffle: (N, n_h, S_h, n_w, S_w, CO) -> (N, n_h*S, n_w*S, CO)
    y = y.transpose(2, 3, 0, 4, 1, 5).reshape(n, n_h * s, n_w * s, co)
    y = y[:, :oh, :ow, :]
    if b is not None:
        y = y + b
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Conventional zero-insertion formulation (the GPU/XLA baseline)
# ---------------------------------------------------------------------------
def deconv2d_zero_insertion(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array],
    stride: int,
    padding: int,
) -> jax.Array:
    """Transposed conv via input dilation: correlate the S-dilated, (K-1-P)-
    padded input with the spatially-flipped kernel.  This is the standard
    formulation the paper contrasts against (zero-insertion wastes
    (S^2-1)/S^2 of the MACs on zeros)."""
    k = w.shape[0]
    wf = jnp.flip(w, axis=(0, 1))
    pad = k - 1 - padding
    return _conv(x, wf, b, pad, stride)


def _conv(x, w, b, pad, lhs_dilation):
    y = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding=((pad, pad), (pad, pad)),
        lhs_dilation=(lhs_dilation, lhs_dilation),
        rhs_dilation=(1, 1),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if b is not None:
        y = y + b
    return y
