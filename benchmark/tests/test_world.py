"""The world is made from the seed alone, in any blocking and layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import world
from benchmark.references import sart_linear

CFG = {"npixel": 60, "nvoxel": 250, "band": 200.0, "floor": 0.02,
       "grid": [2, 5, 25]}
TRAFFIC = {"scale_amplitude": 0.3, "scale_period_frames": 32,
           "base_level": 0.5, "base_blobs": 4, "base_sigma_frac": 0.15,
           "blob_amplitude": 1.0, "blob_sigma_voxels": 3.0,
           "drift_voxels_per_frame": 1.0, "noise": 0.01, "layout_seed": 0,
           "drift_start": [0.3, 0.3, 0.3], "drift_direction": [1.0, 0.6, 0.35]}
SHAPE = (64, 256)  # padded: rows to 8 x 4 devices... and 128 columns
SEED = 2**31 + 12345  # past what 32 signed bits hold


def _one_shot(seed):
    lo, hi = world.seed_words(seed)
    rows = jnp.arange(SHAPE[0], dtype=jnp.int32)
    one = jax.jit(lambda r, a, b: world.rtm_rows(r, 0, SHAPE[1], CFG, a, b))
    return np.asarray(one(rows, np.uint32(lo), np.uint32(hi)))


def _sharding(n_dev):
    return NamedSharding(Mesh(np.array(jax.devices()[:n_dev]), ("rows",)),
                         P("rows", None))


def _built(seed, n_dev, row_block, monkeypatch):
    monkeypatch.setattr(world, "ROW_BLOCK", row_block)
    return np.asarray(world.build_rtm(CFG, seed, _sharding(n_dev), SHAPE))


@pytest.mark.parametrize("n_dev,row_block", [(1, 64), (1, 5), (4, 8),
                                             (4, 3)])
def test_blockwise_fill_equals_one_shot(n_dev, row_block, monkeypatch):
    H = _built(SEED, n_dev, row_block, monkeypatch)
    # equal up to the last bit of exp, which XLA may vectorise by shape
    np.testing.assert_allclose(H, _one_shot(SEED), rtol=3e-6, atol=0)
    assert np.all(H[CFG["npixel"]:] == 0) and np.all(H[:, CFG["nvoxel"]:] == 0)
    logical = H[: CFG["npixel"], : CFG["nvoxel"]]
    assert logical.min() > 0.1 * 0.02 and logical.max() <= 1.02


def test_rtm_is_deterministic_per_seed(monkeypatch):
    a = _built(SEED, 1, 16, monkeypatch)
    b = _built(SEED, 1, 16, monkeypatch)
    c = _built(SEED + 1, 1, 16, monkeypatch)
    np.testing.assert_array_equal(a, b)
    logical = (slice(0, CFG["npixel"]), slice(0, CFG["nvoxel"]))
    assert np.mean(a[logical] != c[logical]) > 0.99


def test_frames_are_h_times_emissivity_with_noise(monkeypatch):
    s = world.Series(CFG, TRAFFIC, SEED, 6)
    F = s.emissivity(SHAPE[1])
    H = _built(SEED, 4, 8, monkeypatch)
    G = world.noiseless_frames(CFG, SEED, _sharding(4), SHAPE, F)
    np.testing.assert_allclose(G, H.astype(np.float64) @ F, rtol=1e-5)
    g = s.noisy(G, CFG["npixel"])
    again = world.Series(CFG, TRAFFIC, SEED, 6).noisy(G, CFG["npixel"])
    np.testing.assert_array_equal(g, again)
    rel = g / G[: CFG["npixel"]].T - 1
    assert 0.005 < rel.std() < 0.02  # 1% multiplicative noise
    # the blob drifts about one voxel a frame, so frames change shape
    step = np.linalg.norm(np.diff(s.blob_path, axis=0), axis=1)
    assert np.all(step <= 1.0 + 1e-9) and step.mean() > 0.5


def test_every_seed_asks_the_same_work():
    a = world.Series(CFG, TRAFFIC, 1, 40)
    b = world.Series(CFG, TRAFFIC, 2**33 + 7, 40)
    np.testing.assert_array_equal(a.emissivity(256), b.emissivity(256))
    G = np.ones((SHAPE[0], 40), np.float32)
    np.testing.assert_array_equal(a.noisy(G, 60), b.noisy(G, 60))


@pytest.mark.parametrize("grid", [(10, 25), (2, 5, 25), (3, 4, 5)])
def test_grid_laplacian_matches_the_reference_stencil(grid):
    r, c, v = world.grid_laplacian(grid)
    nv = int(np.prod(grid))
    x = np.random.default_rng(0).random(SHAPE[1]).astype(np.float32)
    coo = np.zeros(SHAPE[1], np.float64)
    np.add.at(coo, r, v * x[c])
    sten = np.asarray(sart_linear._stencil(jnp.asarray(x), grid))
    np.testing.assert_allclose(sten, coo, atol=1e-5)
    # a graph Laplacian: rows sum to zero, the degree counts neighbours
    np.testing.assert_allclose(np.bincount(r, v, SHAPE[1])[:nv], 0,
                               atol=1e-6)
    assert v[:nv].max() == sum(min(n - 1, 2) for n in grid)


def test_grid_must_hold_nvoxel_and_match_the_drift():
    with pytest.raises(ValueError):
        world.grid_shape(dict(CFG, grid=[5, 25]))
    with pytest.raises(ValueError):
        world.Series(dict(CFG, grid=[10, 25]), TRAFFIC, SEED, 4)


def test_blobs_are_gaussians_on_the_grid():
    s = world.Series(CFG, dict(TRAFFIC, base_level=0.0, base_blobs=0,
                               scale_amplitude=0.0), SEED, 1)
    F = s.emissivity(256)[:250, 0].reshape(CFG["grid"])
    idx = np.indices(CFG["grid"]).reshape(3, -1).T
    d2 = np.sum((idx - s.blob_path[0]) ** 2, axis=1)
    sig = TRAFFIC["blob_sigma_voxels"]
    np.testing.assert_allclose(F.ravel(), np.exp(-d2 / (2 * sig * sig)),
                               rtol=1e-6)
