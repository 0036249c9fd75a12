"""DSE-driven tile autotuner: legality, VMEM clamping, cache behavior."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dse import TPU_V5E
from repro.core.tiling import DeconvGeometry, kernel_vmem_bytes
from repro.kernels import autotune
from repro.kernels.autotune import (
    TileChoice, choose_tiles, clear_cache, fallback_tiles,
    legal_tile_candidates,
)
from repro.kernels.deconv2d import deconv2d, deconv2d_ref

CELEBA_L2 = DeconvGeometry(4, 4, 1024, 512, 4, 2, 1)
MNIST_L2 = DeconvGeometry(7, 7, 256, 128, 4, 2, 1)


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Redirect the autotune cache into the test tmpdir."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    yield tmp_path / "at.json"
    monkeypatch.setattr(autotune, "_cache", None)


def _assert_legal(geom, c: TileChoice, dtype_bytes=4):
    s = geom.stride
    assert c.t_oh % s == 0 and c.t_ow % s == 0
    assert c.t_oh > 0 and c.t_ow > 0 and c.t_ci > 0 and c.t_co > 0
    assert c.t_n > 0
    fp = kernel_vmem_bytes(geom, c.t_oh, c.t_ow, c.t_ci, c.t_co, dtype_bytes,
                           t_n=c.t_n)
    assert fp <= TPU_V5E.onchip_bytes, f"tile {c} exceeds VMEM: {fp}"


@pytest.mark.parametrize("geom", [CELEBA_L2, MNIST_L2,
                                  DeconvGeometry(1, 1, 100, 1024, 4, 1, 0),
                                  DeconvGeometry(32, 32, 128, 3, 4, 2, 1)])
def test_chosen_tiles_legal_and_within_vmem(geom, tmp_cache):
    """Acceptance: the chosen tile is legal (stride-aligned) and within the
    VMEM cap, for every generator-layer geometry."""
    c = choose_tiles(geom, jnp.float32, backend="pallas")
    assert c.source in ("model", "fallback")
    _assert_legal(geom, c)


def test_candidates_all_fit_budget():
    for (t_oh, t_ow, t_ci, t_co, t_n) in legal_tile_candidates(
            CELEBA_L2, batch=16):
        assert t_n <= 16
        assert kernel_vmem_bytes(CELEBA_L2, t_oh, t_ow, t_ci, t_co, 4,
                                 t_n=t_n) <= TPU_V5E.onchip_bytes


def test_fallback_clamps_large_ci_co_layers():
    """Satellite bug: the fixed heuristic used to pick 32x32/128/128 blocks
    regardless of footprint; a fat-channel layer must now be clamped."""
    fat = DeconvGeometry(64, 64, 4096, 4096, 11, 1, 0)
    c = fallback_tiles(fat, dtype_bytes=4)
    _assert_legal(fat, c)
    # and an unclamped 32x32/128/128 choice would NOT have fit
    assert kernel_vmem_bytes(fat, 32, 32, 128, 128, 4) > TPU_V5E.onchip_bytes


def test_cache_roundtrip_and_clear(tmp_cache):
    c1 = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c1.source != "cache"
    assert tmp_cache.exists()
    c2 = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c2.source == "cache"
    assert c2.as_kwargs() == c1.as_kwargs()
    # distinct key per backend/dtype
    c3 = choose_tiles(MNIST_L2, jnp.bfloat16, backend="pallas")
    assert c3.source != "cache"
    clear_cache()
    assert not tmp_cache.exists()
    c4 = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c4.source != "cache"


def test_refine_times_candidates_and_persists(tmp_cache):
    g = DeconvGeometry(4, 4, 8, 8, 4, 2, 1)  # tiny: timing is cheap
    c = choose_tiles(g, jnp.float32, backend="pallas", refine=True,
                     refine_top_k=2)
    assert c.source == "timed"
    _assert_legal(g, c)
    assert choose_tiles(g, jnp.float32, backend="pallas").source == "cache"


def test_refine_not_suppressed_by_model_cache_entry(tmp_cache):
    """A stored model choice must not satisfy a refine=True request — only
    a timed entry does (the refinement then overwrites the model entry)."""
    g = DeconvGeometry(4, 4, 8, 8, 4, 2, 1)
    assert choose_tiles(g, jnp.float32, backend="pallas").source == "model"
    c = choose_tiles(g, jnp.float32, backend="pallas", refine=True,
                     refine_top_k=2)
    assert c.source == "timed"
    # and the timed entry now serves refine=True requests from cache
    c2 = choose_tiles(g, jnp.float32, backend="pallas", refine=True)
    assert c2.source == "cache"


def test_sparse_plan_tile_mismatch_rejected(tmp_cache, rng):
    from repro.kernels.deconv2d_sparse import deconv2d_sparse, make_sparse_plan

    x = jnp.array(rng.randn(1, 7, 7, 16), jnp.float32)
    w = (rng.randn(4, 4, 16, 32) * 0.1).astype(np.float32)
    plan = make_sparse_plan(w, 2, 1, t_ci=8, t_co=8)  # 4 C_out tiles
    with pytest.raises(ValueError, match="C_out tiles"):
        deconv2d_sparse(x, jnp.asarray(w), None, 2, 1,
                        t_ci=8, t_co=32, plan=plan)  # 1 C_out tile


def test_batch_tile_options_never_exceed_batch():
    """Review regression: a non-power-of-two batch must not enumerate a
    t_n beyond the batch (it would be scored with an MXU fill the clamped
    kernel can't reach)."""
    from repro.kernels.autotune import _batch_tile_options

    assert _batch_tile_options(6) == [1, 2, 4, 6]
    assert _batch_tile_options(1) == [1]
    assert _batch_tile_options(64) == [1, 2, 4, 8, 16, 32, 64]
    assert _batch_tile_options(100) == [1, 2, 4, 8, 16, 32, 64]  # cap
    for b in range(1, 70):
        assert all(t <= b for t in _batch_tile_options(b))


def test_choice_batch_aware_t_n(tmp_cache):
    """The batch tile is chosen jointly: batch=1 keeps the per-image grid,
    a batch-64 request on the row-starved CelebA L1 batch-fuses, and t_n
    never exceeds the batch it was fitted to."""
    l1 = DeconvGeometry(1, 1, 100, 1024, 4, 1, 0)
    c1 = choose_tiles(l1, jnp.float32, backend="pallas", batch=1)
    assert c1.t_n == 1
    c64 = choose_tiles(l1, jnp.float32, backend="pallas", batch=64)
    assert 1 < c64.t_n <= 64
    _assert_legal(l1, c64)
    # distinct cache entries per batch (the key carries the bucket)
    assert choose_tiles(l1, jnp.float32, backend="pallas",
                        batch=64).source == "cache"
    assert choose_tiles(l1, jnp.float32, backend="pallas",
                        batch=32).source != "cache"


def test_fallback_t_n_targets_mxu_rows(tmp_cache):
    """The clamped heuristic grows t_n (powers of two within the batch)
    until the tap matmuls reach ~128 contraction rows."""
    l1 = DeconvGeometry(1, 1, 100, 1024, 4, 1, 0)  # 4x4 out -> 16 rows/img
    c = fallback_tiles(l1, batch=64)
    assert c.t_n * (c.t_oh // l1.stride) * (c.t_ow // l1.stride) >= 128
    _assert_legal(l1, c)
    assert fallback_tiles(l1, batch=1).t_n == 1
    # a layer already at >=128 spatial rows stays per-image
    fat = DeconvGeometry(32, 32, 128, 3, 4, 2, 1)
    assert fallback_tiles(fat, batch=64).t_n == 1


def test_stale_v1_schema_entry_not_served(tmp_cache):
    """Satellite: a cache entry without the batch tile (the v1 4-tuple
    schema) must be dropped on load, not silently served as stale tiles."""
    import json

    from repro.kernels.autotune import cache_key

    key = cache_key(MNIST_L2, jnp.float32, "pallas")
    stale = {key: {"t_oh": 2, "t_ow": 2, "t_ci": 8, "t_co": 8,
                   "source": "timed", "attainable_ops": 1.0,
                   "vmem_bytes": 1}}   # no t_n: pre-t_n schema
    tmp_cache.write_text(json.dumps(stale))
    c = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c.source != "cache"
    assert c.as_kwargs() != {"t_oh": 2, "t_ow": 2, "t_ci": 8, "t_co": 8,
                             "t_n": 1}


def test_v3_schema_keys_dropped_on_load(tmp_cache):
    """Satellite: v4 derives keys from `DeconvPlan.stable_hash` instead of
    the v3 hand-assembled tuple string, so a v3 key — whose format could
    silently omit a new ranking input — is stale even when its value shape
    is valid.  Every key from a different schema version is dropped on
    load, and the next store persists a clean v4-only file."""
    import json

    from repro.kernels.autotune import _CACHE_VERSION, cache_key

    assert _CACHE_VERSION == 5
    key4 = cache_key(MNIST_L2, jnp.float32, "pallas")
    assert key4.startswith("v5|")
    # a v3-era key: hand-assembled readable tuple under the old version
    key3 = ("v3|cpu|tpu-v5e|pallas|float32|n1|i7x7|c256>128|k4s2p1")
    entry = {"t_oh": 2, "t_ow": 2, "t_ci": 8, "t_co": 8, "t_n": 1,
             "source": "timed", "attainable_ops": 1.0, "vmem_bytes": 1}
    tmp_cache.write_text(json.dumps({key3: entry}))
    c = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c.source != "cache"
    assert c.as_kwargs() != {"t_oh": 2, "t_ow": 2, "t_ci": 8, "t_co": 8,
                             "t_n": 1}
    blob = json.loads(tmp_cache.read_text())
    assert key3 not in blob            # stale schema purged on re-store
    assert key4 in blob


def test_v4_cache_key_is_plan_hash(tmp_cache):
    """The v4 key is derived from the plan's tile-scope stable hash: the
    same request hashes identically through either entry point, and every
    tile-relevant planning input (dtype, batch, backend, epilogue output
    width) produces a distinct key."""
    from repro.kernels.autotune import cache_key, plan_cache_key
    from repro.plan import DeconvPlan

    plan = DeconvPlan(geometry=MNIST_L2, batch=8, dtype="float32",
                      backend="pallas")
    key = cache_key(MNIST_L2, jnp.float32, "pallas", batch=8)
    assert key == plan_cache_key(plan)
    assert plan.stable_hash(scope="tiles") in key
    # a resolved plan keys identically to the bare request (the tiles are
    # the cached payload, not part of the key)
    resolved = choose_tiles(MNIST_L2, jnp.float32, backend="pallas", batch=8)
    import dataclasses
    assert plan_cache_key(dataclasses.replace(plan, tiles=resolved)) == key
    variants = [
        cache_key(MNIST_L2, jnp.int8, "pallas", batch=8),
        cache_key(MNIST_L2, jnp.float32, "pallas_sparse", batch=8),
        cache_key(MNIST_L2, jnp.float32, "pallas", batch=64),
        cache_key(MNIST_L2, jnp.float32, "pallas", batch=8,
                  out_dtype_bytes=4),
        cache_key(CELEBA_L2, jnp.float32, "pallas", batch=8),
    ]
    assert len(set(variants + [key])) == len(variants) + 1


def test_int8_dtype_distinct_cache_key(tmp_cache):
    """The dtype has always been in the key; v3 additionally ranks with
    it, so int8 and fp32 requests tune (and cache) independently."""
    c8 = choose_tiles(MNIST_L2, jnp.int8, backend="pallas")
    assert c8.source != "cache"
    assert choose_tiles(MNIST_L2, jnp.int8, backend="pallas").source == "cache"
    assert choose_tiles(MNIST_L2, jnp.float32,
                        backend="pallas").source != "cache"
    _assert_legal(MNIST_L2, c8, dtype_bytes=1)


def test_corrupt_cache_recovery(tmp_cache):
    """Corrupt JSON (truncated write, hand edit) and malformed entries
    recover to a re-tune instead of crashing or serving garbage."""
    import json

    from repro.kernels import autotune
    from repro.kernels.autotune import cache_key

    tmp_cache.write_text("{not json")
    c = choose_tiles(MNIST_L2, jnp.float32, backend="pallas")
    assert c.source == "model"
    _assert_legal(MNIST_L2, c)
    # the re-tuned entry was persisted over the corruption and now serves
    assert choose_tiles(MNIST_L2, jnp.float32,
                        backend="pallas").source == "cache"
    # malformed entry values (wrong types / non-dict) are dropped on load
    autotune._cache = None
    blob = json.loads(tmp_cache.read_text())
    blob[cache_key(CELEBA_L2, jnp.float32, "pallas")] = "bogus"
    blob[cache_key(CELEBA_L2, jnp.bfloat16, "pallas")] = {"t_oh": "four"}
    tmp_cache.write_text(json.dumps(blob))
    assert choose_tiles(MNIST_L2, jnp.float32,
                        backend="pallas").source == "cache"
    c2 = choose_tiles(CELEBA_L2, jnp.float32, backend="pallas")
    assert c2.source != "cache"
    _assert_legal(CELEBA_L2, c2)


def test_cache_roundtrip_includes_t_n(tmp_cache):
    """A batch-fused choice persists t_n and serves it back verbatim."""
    l1 = DeconvGeometry(1, 1, 100, 1024, 4, 1, 0)
    c = choose_tiles(l1, jnp.float32, backend="pallas", batch=64)
    assert c.t_n > 1
    hit = choose_tiles(l1, jnp.float32, backend="pallas", batch=64)
    assert hit.source == "cache"
    assert hit.as_kwargs() == c.as_kwargs()


def test_autotuned_kernel_matches_reference(tmp_cache, rng):
    """End to end: tiles resolved by the autotuner produce correct output."""
    x = jnp.array(rng.randn(2, 7, 7, 16), jnp.float32)
    w = jnp.array(rng.randn(4, 4, 16, 24) * 0.1, jnp.float32)
    b = jnp.array(rng.randn(24), jnp.float32)
    y = deconv2d(x, w, b, 2, 1)  # no explicit tiles -> autotuner
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(deconv2d_ref(x, w, b, 2, 1)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype_bytes", [4, 1], ids=["f32", "int8"])
@pytest.mark.parametrize("name", ["mnist", "celeba", "sr", "denoise"])
def test_candidates_satisfy_mosaic_block_rules(name, dtype_bytes):
    """Every tile the autotuner can pick passes the block-shape rules the
    chip's compiler enforces and interpret mode does not: sublane-aligned
    W windows, lane-multiple or whole-dim channel blocks, t_co <= 128."""
    from repro import workloads
    from repro.core.tiling import SUBLANE, halo_tile
    from repro.kernels.deconv2d.kernel import check_mosaic_tiles

    def up(x, m):
        return -(-x // m) * m

    for g in workloads.get(name).cfg.geometries():
        cands = legal_tile_candidates(g, dtype_bytes, batch=64)
        assert cands, g
        for t_oh, t_ow, t_ci, t_co, t_n in cands:
            ht_w = halo_tile(t_ow, g.kernel, g.stride, g.padding,
                             align=SUBLANE)
            check_mosaic_tiles(ht_w, up(g.out_w, t_ow) // t_ow, t_ci,
                               up(g.c_in, t_ci), t_co, up(g.c_out, t_co),
                               int8=dtype_bytes == 1)


def test_refine_counts_refused_candidates_and_raises_when_all_fail(
        tmp_cache, monkeypatch):
    """A candidate the compiler refuses is counted, labelled with the
    candidate, and skipped; when every candidate is refused the call
    raises instead of falling back to the model's pick."""
    import jax

    from repro.obs import metrics as obsmetrics

    refused = obsmetrics.default_registry().counter(
        "autotune.refine_failures")
    before = refused.total()
    timed = []

    def first_refused(geom, c, dtype, backend, batch=1):
        timed.append(c)
        if len(timed) == 1:
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel")
        return float(len(timed))

    monkeypatch.setattr(autotune, "_time_candidate", first_refused)
    c = choose_tiles(CELEBA_L2, jnp.float32, backend="pallas", refine=True,
                     refine_top_k=3, batch=8, use_cache=False)
    assert len(timed) == 3
    assert c.source == "timed" and c == timed[1]   # fastest that compiled
    assert refused.total() == before + 1

    def all_refused(geom, c, dtype, backend, batch=1):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: vmem")

    monkeypatch.setattr(autotune, "_time_candidate", all_refused)
    with pytest.raises(RuntimeError, match="every refine candidate"):
        choose_tiles(CELEBA_L2, jnp.float32, backend="pallas", refine=True,
                     refine_top_k=3, batch=8, use_cache=False)
