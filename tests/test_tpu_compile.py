"""Compile-only checks of the deconv Pallas kernels for a TPU v5e.

Each case lowers one kernel at the tiles the autotuner picks and compiles
it with the TPU compiler for a described (not attached) ``v5e:2x2`` chip,
so Mosaic's block-shape, alignment and VMEM rules are enforced here even
though the tests run on the CPU, where the kernels otherwise execute in
interpret mode.  Nothing runs; a pass says the chip's compiler accepts the
kernel, nothing about its results or speed.  The topology is described
inside a fixture: only the worker that runs this file loads the TPU
library."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune import choose_tiles
from repro.kernels.deconv2d.int8 import _deconv2d_int8_jit
from repro.kernels.deconv2d.ops import _deconv2d_jit
from repro.kernels.deconv2d_sparse.ops import (_deconv2d_sparse_jit,
                                               make_sparse_plan)
from repro.models.dcnn import CELEBA_DCNN, PIX2PIX_UNET256, dot_precision

LAYERS = CELEBA_DCNN.geometries()
LAST = len(LAYERS) - 1
BUCKETS = (1, 64)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(sharding, *specs):
    return tuple(jax.ShapeDtypeStruct(s, d, sharding=sharding)
                 for s, d in specs)


def _assert_compiles(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("batch", BUCKETS)
@pytest.mark.parametrize("layer", range(len(LAYERS)))
def test_dense_f32_compiles_for_v5e(one_chip, layer, batch):
    g = LAYERS[layer]
    t = choose_tiles(g, jnp.float32, "pallas", batch=batch, use_cache=False)
    args = _shapes(one_chip,
                   ((batch, g.in_h, g.in_w, g.c_in), jnp.float32),
                   ((g.kernel, g.kernel, g.c_in, g.c_out), jnp.float32),
                   ((g.c_out,), jnp.float32))
    _assert_compiles(_deconv2d_jit.lower(
        *args, g.stride, g.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n,
        CELEBA_DCNN.layers[layer].activation, False))


@pytest.mark.parametrize("layer", (14, 15))
def test_unet_decoder_compiles_for_v5e(one_chip, layer):
    """The U-Net's two largest decoder kernels at its served bucket 16:
    d7 (64x64x256 -> 128x128x64) and the output layer y (128x128x128 ->
    256x256x3), the largest inputs any cell gives the kernel, with float32
    products (the U-Net's ``dot_precision``)."""
    g = PIX2PIX_UNET256.geometries()[layer]
    assert (g.in_h, g.c_in) == ((64, 256) if layer == 14 else (128, 128))
    t = choose_tiles(g, jnp.float32, "pallas", batch=16, use_cache=False)
    args = _shapes(one_chip,
                   ((16, g.in_h, g.in_w, g.c_in), jnp.float32),
                   ((g.kernel, g.kernel, g.c_in, g.c_out), jnp.float32),
                   ((g.c_out,), jnp.float32))
    with dot_precision(PIX2PIX_UNET256):
        _assert_compiles(_deconv2d_jit.lower(
            *args, g.stride, g.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co,
            t.t_n, PIX2PIX_UNET256.layers[layer].activation, False, layer))


@pytest.mark.parametrize("batch", BUCKETS)
@pytest.mark.parametrize("layer", (1, LAST))
def test_int8_compiles_for_v5e(one_chip, layer, batch):
    g = LAYERS[layer]
    last = layer == LAST   # the last layer's epilogue emits f32 images
    t = choose_tiles(g, jnp.int8, "pallas", batch=batch, use_cache=False,
                     out_dtype_bytes=4 if last else None)
    args = _shapes(one_chip,
                   ((batch, g.in_h, g.in_w, g.c_in), jnp.int8),
                   ((g.kernel, g.kernel, g.c_in, g.c_out), jnp.int8),
                   ((g.c_out,), jnp.float32), ((g.c_out,), jnp.float32))
    _assert_compiles(_deconv2d_int8_jit.lower(
        *args, g.stride, g.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n,
        CELEBA_DCNN.layers[layer].activation, None if last else 0.05,
        False))


@pytest.mark.parametrize("batch", BUCKETS)
@pytest.mark.parametrize("layer", (1, LAST))
def test_sparse_compiles_for_v5e(one_chip, layer, batch):
    g = LAYERS[layer]
    t = choose_tiles(g, jnp.float32, "pallas_sparse", batch=batch,
                     use_cache=False)
    w = np.random.RandomState(0).randn(
        g.kernel, g.kernel, g.c_in, g.c_out).astype(np.float32)
    ci_idx, valid, taps = make_sparse_plan(w, g.stride, g.padding, t.t_ci,
                                           t.t_co)
    args = _shapes(one_chip,
                   ((batch, g.in_h, g.in_w, g.c_in), jnp.float32),
                   (w.shape, jnp.float32), ((g.c_out,), jnp.float32),
                   (ci_idx.shape, jnp.int32), (valid.shape, jnp.int32),
                   (taps.shape, jnp.int32))
    _assert_compiles(_deconv2d_sparse_jit.lower(
        *args, g.stride, g.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n,
        CELEBA_DCNN.layers[layer].activation, False))


@pytest.mark.parametrize("kind", ["dense", "dense_thin", "int8", "sparse"])
def test_layer_index_names_the_kernel_for_v5e(one_chip, kind):
    """A layer's index reaches its kernel's name in the compiled program
    (``deconv2d_l1_...``), which is how a profile tells the layers apart;
    the unnamed kernel keeps the bare name.  The dense kernel of a thin
    output layer (the 3-channel image, whose tile folds its taps) is
    ``halo_tapfold``."""
    from repro.kernels.deconv2d.kernel import kernel_name

    layer = LAST if kind == "dense_thin" else 1
    g, act = LAYERS[layer], CELEBA_DCNN.layers[layer].activation
    dtype = jnp.int8 if kind == "int8" else jnp.float32
    t = choose_tiles(g, dtype, "pallas_sparse" if kind == "sparse"
                     else "pallas", batch=1, use_cache=False)
    x = ((1, g.in_h, g.in_w, g.c_in), dtype)
    w = ((g.kernel, g.kernel, g.c_in, g.c_out), dtype)
    c = ((g.c_out,), jnp.float32)
    tiles = (g.stride, g.padding, t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n)
    if kind in ("dense", "dense_thin"):
        base = "halo_tapfold" if kind == "dense_thin" else "halo_reverse_loop"
        args = _shapes(one_chip, x, w, c)
        lower = lambda layer: _deconv2d_jit.lower(  # noqa: E731
            *args, *tiles, act, False, layer)
    elif kind == "int8":
        base = "int8_halo_reverse_loop"
        args = _shapes(one_chip, x, w, c, c)
        lower = lambda layer: _deconv2d_int8_jit.lower(  # noqa: E731
            *args, *tiles, act, 0.05, False, layer)
    else:
        base = "sparse_reverse_loop"
        wz = np.ones(w[0], np.float32)
        tables = make_sparse_plan(wz, g.stride, g.padding, t.t_ci, t.t_co)
        args = _shapes(one_chip, x, w, c,
                       *((tb.shape, jnp.int32) for tb in tables))
        lower = lambda layer: _deconv2d_sparse_jit.lower(  # noqa: E731
            *args, *tiles, act, False, layer)
    named = lower(1).compile().as_text()
    assert kernel_name(base, 1) == f"deconv2d_l1_{base}"
    assert f"deconv2d_l1_{base}" in named
    assert f"deconv2d_{base}" in lower(None).compile().as_text()
