"""Halo-aware input tiling: BlockSpec geometry, parity vs the paper's
Algorithm 1 oracle on awkward shapes, fused epilogue, traffic invariants."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core.deconv import deconv2d_algorithm1_numpy
from repro.core.tiling import (
    SUBLANE, DeconvGeometry, deconv_traffic, exact_input_extent,
    full_image_traffic, halo_tile, kernel_vmem_bytes, out_size,
)
from repro.kernels.deconv2d import deconv2d, deconv2d_ref
from repro.kernels.deconv2d.kernel import x_halo_blockspec


# ---------------------------------------------------------------------------
# halo-tile geometry
# ---------------------------------------------------------------------------
def test_halo_extent_is_exact_input_extent():
    """The streamed window is exactly the max-over-tiles input span — no
    over-read (the whole point of the tentpole)."""
    for k, s, p in itertools.product(range(1, 8), range(1, 5), range(0, 4)):
        if p >= k:
            continue
        for tm in (1, 2, 3, 5):
            t = tm * s
            ht = halo_tile(t, k, s, p)
            assert ht.extent == exact_input_extent(t, k, s, p)
            assert ht.step == t // s
            assert ht.base >= 0  # host left-halo keeps every window in bounds
            assert ht.overlap == ht.extent - ht.step


def test_x_blockspec_shape_and_index_map():
    """Acceptance: the x BlockSpec no longer spans the full padded input —
    the per-program block is the halo window and its index map follows the
    *output* grid (element offsets advancing by t_oh/S per tile)."""
    k, s, p = 4, 2, 1
    t_oh, t_ow, t_ci = 8, 8, 32
    ht = halo_tile(t_oh, k, s, p)
    bs = x_halo_blockspec(ht, ht, t_ci, 1, n_tiles_w=8, n_ci=3)
    assert tuple(bs.block_shape) == tuple(
        pl.Element(d) for d in (1, ht.extent, ht.extent, t_ci))
    assert ht.extent == 6  # 8/2 + delta span 2: constant, image-independent
    # index map follows the output-tile grid, not a constant (0, 0) base
    for oh_t, ow_t, ci_t in [(0, 0, 0), (1, 0, 0), (2, 3, 1), (5, 7, 2)]:
        got = bs.index_map(1, oh_t, ow_t, 0, ci_t)
        assert got == (1, oh_t * ht.step + ht.base,
                       ow_t * ht.step + ht.base, ci_t * t_ci)
    # a dim with a single window gets its constant offset, which Mosaic can
    # prove tile-aligned without knowing the grid index is 0
    one = x_halo_blockspec(ht, ht, t_ci, 1, n_tiles_w=1, n_ci=1)
    assert one.index_map(1, 2, 0, 0, 0) == (1, 2 * ht.step + ht.base,
                                            ht.base, 0)


@pytest.mark.parametrize("k,s,p", [(4, 2, 1), (5, 2, 2), (4, 1, 0),
                                   (7, 1, 0), (5, 3, 1)])
def test_sublane_aligned_halo_tile(k, s, p):
    """The W window Mosaic streams starts on a sublane boundary and spans
    whole sublane tiles, yet still holds every row the tile's taps read:
    the alignment moves rows in front of the window, never out of it."""
    from repro.core.offsets import make_phase_plan

    plan = make_phase_plan(k, s, p)
    for t in (SUBLANE * s, 2 * SUBLANE * s, s, 3 * s):
        exact = halo_tile(t, k, s, p)
        ht = halo_tile(t, k, s, p, align=SUBLANE)
        assert ht.base % SUBLANE == 0 and ht.extent % SUBLANE == 0
        assert ht.base <= exact.base and ht.step == exact.step
        assert ht.extent >= exact.extent
        # tap rows of the tile: [local(delta_min), local(delta_max) + step)
        assert ht.local_offset(plan.delta_min) >= 0
        assert ht.local_offset(plan.delta_max) + ht.step <= ht.extent
        # the same input rows as the unaligned window, window-relative
        for d in (plan.delta_min, plan.delta_max):
            assert (ht.base + ht.local_offset(d)
                    == exact.base + exact.local_offset(d))


def test_windows_cover_padded_input_exactly():
    """The last tile's window ends exactly at the padded extent the ops
    wrapper produces (no slack, no out-of-bounds)."""
    from repro.core.offsets import make_phase_plan

    for k, s, p, ih, t in [(4, 2, 1, 7, 4), (5, 2, 2, 4, 4), (3, 3, 1, 8, 9),
                           (7, 1, 0, 1, 7), (4, 2, 1, 16, 8)]:
        plan = make_phase_plan(k, s, p)
        oh = out_size(ih, k, s, p)
        ohp = -(-oh // t) * t
        n_h_pad = ohp // s
        pad_l = plan.left_halo
        pad_rh = max(0, (n_h_pad - 1 + plan.delta_max) - (ih - 1))
        ihp = ih + pad_l + pad_rh
        ht = halo_tile(t, k, s, p)
        need = ht.min_padded_extent(ohp // t)
        assert need <= ihp
        # ...and is tight whenever padding was actually added on the right
        if pad_rh > 0:
            assert need == ihp


# ---------------------------------------------------------------------------
# parity vs Algorithm 1 on non-stride-aligned / non-square shapes
# ---------------------------------------------------------------------------
ALG1_GEOMS = [
    # (ih, iw, ci, co, k, s, p, t) — OH=7, S=2, K=5: the CelebA-layer
    # geometry from the issue (odd output, ragged last tile)
    (4, 4, 6, 5, 5, 2, 2, 4),
    # non-square input AND output (oh=7, ow=11)
    (4, 6, 3, 4, 5, 2, 2, 4),
    # non-square with non-dividing tile on both dims
    (5, 3, 4, 7, 4, 2, 1, 6),
    # stride-3 ragged edge
    (4, 5, 2, 3, 5, 3, 1, 6),
]


@pytest.mark.parametrize("geom", ALG1_GEOMS)
def test_kernel_matches_algorithm1(geom, rng):
    ih, iw, ci, co, k, s, p, t = geom
    x = rng.randn(2, ih, iw, ci).astype(np.float32)
    w = (rng.randn(k, k, ci, co) * 0.1).astype(np.float32)
    b = (rng.randn(co) * 0.1).astype(np.float32)
    y = deconv2d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), s, p,
                 t_oh=t, t_ow=t)
    for n in range(x.shape[0]):
        y_ref, _ = deconv2d_algorithm1_numpy(x[n], w, b, s, p)
        np.testing.assert_allclose(
            np.asarray(y[n]), y_ref.astype(np.float32), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("geom", ALG1_GEOMS)
def test_batch_fused_kernel_matches_algorithm1(geom, rng):
    """The batch-tiled grid (t_n=2, batch 5: ragged last batch tile) is
    bit-compatible with the per-image Algorithm 1 oracle on the same
    awkward shapes."""
    ih, iw, ci, co, k, s, p, t = geom
    x = rng.randn(5, ih, iw, ci).astype(np.float32)
    w = (rng.randn(k, k, ci, co) * 0.1).astype(np.float32)
    b = (rng.randn(co) * 0.1).astype(np.float32)
    y = deconv2d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), s, p,
                 t_oh=t, t_ow=t, t_n=2)
    for n in range(x.shape[0]):
        y_ref, _ = deconv2d_algorithm1_numpy(x[n], w, b, s, p)
        np.testing.assert_allclose(
            np.asarray(y[n]), y_ref.astype(np.float32), rtol=1e-4, atol=1e-4)


def test_x_blockspec_batch_tile():
    """The batch-tiled x BlockSpec streams t_n images' windows per program;
    the element-offset index map advances by t_n elements on the batch dim."""
    k, s, p = 4, 2, 1
    t_oh, t_ci, t_n = 8, 32, 4
    ht = halo_tile(t_oh, k, s, p)
    bs = x_halo_blockspec(ht, ht, t_ci, t_n, n_tiles_w=8, n_ci=3)
    assert tuple(bs.block_shape) == tuple(
        pl.Element(d) for d in (t_n, ht.extent, ht.extent, t_ci))
    for nb, oh_t, ow_t, ci_t in [(0, 0, 0, 0), (3, 1, 2, 1), (7, 5, 0, 2)]:
        got = bs.index_map(nb, oh_t, ow_t, 0, ci_t)
        assert got == (nb * t_n, oh_t * ht.step + ht.base,
                       ow_t * ht.step + ht.base, ci_t * t_ci)


@pytest.mark.parametrize("activation", ["relu", "tanh"])
def test_fused_epilogue_matches_unfused(activation, rng):
    x = jnp.array(rng.randn(2, 5, 7, 8), jnp.float32)
    w = jnp.array(rng.randn(4, 4, 8, 12) * 0.1, jnp.float32)
    b = jnp.array(rng.randn(12) * 0.1, jnp.float32)
    y = deconv2d(x, w, b, 2, 1, activation=activation)
    y_ref = deconv2d_ref(x, w, b, 2, 1)
    y_ref = jnp.maximum(y_ref, 0) if activation == "relu" else jnp.tanh(y_ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_sparse_epilogue(rng):
    from repro.kernels.deconv2d_sparse import deconv2d_sparse

    x = jnp.array(rng.randn(1, 7, 7, 16), jnp.float32)
    w = jnp.array(rng.randn(4, 4, 16, 16) * 0.1, jnp.float32)
    b = jnp.array(rng.randn(16), jnp.float32)
    y = deconv2d_sparse(x, w, b, 2, 1, t_ci=8, t_co=8, activation="relu")
    y_ref = jnp.maximum(deconv2d_ref(x, w, b, 2, 1), 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# traffic model invariants
# ---------------------------------------------------------------------------
def test_in_bytes_per_tile_independent_of_image_size():
    """Acceptance: modeled HBM bytes/tile do not grow with the image."""
    per_tile = set()
    for in_hw in (8, 16, 32, 64, 128):
        g = DeconvGeometry(in_hw, in_hw, 64, 16, 4, 2, 1)
        t = deconv_traffic(g, 16, 16, 64, 16, 4)
        per_tile.add((t.in_bytes_per_tile, t.w_bytes_per_tile,
                      t.out_bytes_per_tile))
    assert len(per_tile) == 1


def test_halo_traffic_below_full_image_when_tiled():
    g = DeconvGeometry(32, 32, 128, 3, 4, 2, 1)  # CelebA L5
    halo = deconv_traffic(g, 32, 32, 128, 8, 4)
    full = full_image_traffic(g, 32, 32, 128, 8, 4)
    # 4 spatial tiles share halos instead of re-streaming the image
    assert halo.total_bytes < full.total_bytes
    assert halo.in_bytes_per_tile < full.in_bytes_per_tile


def test_kernel_vmem_bytes_monotone_in_tiles():
    g = DeconvGeometry(16, 16, 256, 256, 4, 2, 1)
    small = kernel_vmem_bytes(g, 8, 8, 64, 64)
    big = kernel_vmem_bytes(g, 32, 32, 256, 256)
    assert small < big
    # ...and in the batch tile: x/y/acc scale with t_n, weights do not
    assert kernel_vmem_bytes(g, 8, 8, 64, 64, t_n=4) > small
    assert kernel_vmem_bytes(g, 8, 8, 64, 64, t_n=4) < 4 * small


def test_batched_traffic_amortizes_weights():
    """The batch-fused traffic model: per-image input/output bytes are
    t_n-invariant while per-image *weight* bytes fall by t_n (one slab per
    CI step serves t_n images) — the spatio-temporal amortization."""
    from repro.core.tiling import deconv_traffic_batched

    g = DeconvGeometry(1, 1, 100, 1024, 4, 1, 0)  # CelebA L1
    batch = 64
    t1 = deconv_traffic_batched(g, batch, 1, 4, 4, 104, 128)
    t64 = deconv_traffic_batched(g, batch, 64, 4, 4, 104, 128)
    # total bytes strictly fall with batch fusion...
    assert t64.total_bytes < t1.total_bytes
    # ...input stream per image unchanged (n_tiles shrank by 64, window x64)
    assert t64.in_bytes_per_tile == 64 * t1.in_bytes_per_tile
    assert t64.n_tiles * 64 == t1.n_tiles
    # ...and the whole saving is the amortized weight stream
    w1 = t1.n_tiles * t1.n_ci_steps * t1.w_bytes_per_tile
    w64 = t64.n_tiles * t64.n_ci_steps * t64.w_bytes_per_tile
    assert w64 * 64 == w1
    assert t1.total_bytes - t64.total_bytes == w1 - w64


def test_batched_attainable_improves_on_row_starved_layer():
    """DSE: on the 4x4-output fat-channel CelebA L1 (16 rows vs the 128-row
    MXU) the modeled attainable throughput strictly improves with t_n."""
    from repro.core.dse import TPU_V5E, tile_attainable

    g = DeconvGeometry(1, 1, 100, 1024, 4, 1, 0)
    a1 = tile_attainable(g, 4, 4, 104, 128, TPU_V5E, t_n=1, batch=64)
    a8 = tile_attainable(g, 4, 4, 104, 128, TPU_V5E, t_n=8, batch=64)
    a64 = tile_attainable(g, 4, 4, 104, 128, TPU_V5E, t_n=64, batch=64)
    assert a1.attainable_ops < a8.attainable_ops < a64.attainable_ops
