"""Tests for the native C++ data loader (dataio): build, numerics vs the
Python path, determinism, and pipeline integration."""

import numpy as np
import pytest

from deeplearning_cfn_tpu import dataio
from deeplearning_cfn_tpu.data.pipeline import (
    ArraySource,
    DataPipeline,
    augment_crop_flip,
)

pytestmark = pytest.mark.skipif(not dataio.available(),
                                reason="no C++ toolchain for dataio")


def test_builds_and_loads():
    assert dataio.get_lib() is not None


def test_gather_matches_numpy():
    rng = np.random.RandomState(0)
    src = rng.rand(32, 8, 8, 3).astype(np.float32)
    idx = np.asarray([5, 1, 30, 5], np.int32)
    out = dataio.gather_augment(src, idx, pad=4, seed=7, augment=False)
    np.testing.assert_array_equal(out, src[idx])


def test_gather_rows_matches_numpy():
    rng = np.random.RandomState(0)
    f = rng.rand(16, 10).astype(np.float32)
    i = rng.randint(0, 100, (16, 7)).astype(np.int32)
    idx = np.asarray([3, 3, 0, 15], np.int32)
    np.testing.assert_array_equal(dataio.gather_rows(f, idx), f[idx])
    np.testing.assert_array_equal(dataio.gather_rows(i, idx), i[idx])


def test_augment_deterministic_and_valid():
    rng = np.random.RandomState(1)
    src = rng.rand(8, 16, 16, 3).astype(np.float32)
    idx = np.arange(8, dtype=np.int32)
    a = dataio.gather_augment(src, idx, pad=4, seed=99, augment=True)
    b = dataio.gather_augment(src, idx, pad=4, seed=99, augment=True,
                              nthreads=1)  # thread count must not matter
    np.testing.assert_array_equal(a, b)
    c = dataio.gather_augment(src, idx, pad=4, seed=100, augment=True)
    assert not np.array_equal(a, c)
    # Every output pixel value exists in the source image (crop/flip only
    # rearranges reflect-padded pixels).
    for k in range(8):
        assert np.isin(a[k].ravel(), src[k].ravel()).all()


def test_pipeline_uses_native_path():
    rng = np.random.RandomState(2)
    src = ArraySource({
        "image": rng.rand(64, 8, 8, 3).astype(np.float32),
        "label": rng.randint(0, 10, 64).astype(np.int32),
    })
    pipe = DataPipeline(src, local_batch=16, seed=0,
                        augment=augment_crop_flip, prefetch=0,
                        process_index=0, process_count=1, native=True)
    assert pipe._native
    batches = list(pipe.one_epoch(0))
    assert len(batches) == 4
    assert batches[0]["image"].shape == (16, 8, 8, 3)
    assert batches[0]["label"].dtype == np.int32
    # Same pipeline twice → identical stream (seeded augmentation).
    batches2 = list(pipe.one_epoch(0))
    np.testing.assert_array_equal(batches[0]["image"],
                                  batches2[0]["image"])
    # Python fallback yields the same examples (labels), different aug RNG.
    pipe_py = DataPipeline(src, local_batch=16, seed=0,
                           augment=augment_crop_flip, prefetch=0,
                           process_index=0, process_count=1, native=False)
    np.testing.assert_array_equal(batches[0]["label"],
                                  next(iter(pipe_py.one_epoch(0)))["label"])


@pytest.fixture()
def fresh_loader(monkeypatch, tmp_path):
    """The loader pointed at a private copy of the source, unloaded."""
    import shutil

    src = tmp_path / "dataio.cpp"
    shutil.copy(dataio._SRC, src)
    monkeypatch.setattr(dataio, "_SRC", str(src))
    monkeypatch.setattr(dataio, "_lib", None)
    monkeypatch.setattr(dataio, "_tried", False)
    monkeypatch.setattr(dataio, "_status", "not loaded yet")
    return src


def test_stale_library_with_newer_mtime_is_rebuilt_not_loaded(fresh_loader):
    """The cached library is keyed on a hash of the source, not on mtime
    (which a copied tree does not keep): a library built from an older
    source is not loaded however new it looks."""
    import os
    import time

    src = fresh_loader
    assert dataio.get_lib() is not None
    first = dataio._lib_path()
    assert os.path.exists(first) and "built now" in dataio.status()

    # The source changes; the old library gets the newest mtime around.
    with open(src, "a") as fh:
        fh.write("\n// edited\n")
    future = time.time() + 3600
    os.utime(first, (future, future))
    assert os.path.getmtime(first) > os.path.getmtime(src)
    dataio._lib, dataio._tried = None, False
    assert dataio.get_lib() is not None
    second = dataio._lib_path()
    assert second != first and os.path.exists(second)
    assert "built now" in dataio.status()
    assert not os.path.exists(first)  # dead weight, removed by the build

    # Unchanged source: the cached library is found again, not rebuilt.
    dataio._lib, dataio._tried = None, False
    assert dataio.get_lib() is not None
    assert "cached" in dataio.status() and dataio._lib_path() == second


def test_failed_build_is_visible_and_degrades(fresh_loader, capfd):
    """A build that was attempted and failed prints the compiler's error
    once and says so in status(); the Python loader takes over."""
    with open(fresh_loader, "w") as fh:
        fh.write("this is not C++\n")
    assert dataio.get_lib() is None
    assert not dataio.available()
    assert "python loader (build failed" in dataio.status()
    err = capfd.readouterr().err
    assert err.count("native loader build failed") == 1
    assert "error" in err  # the compiler's own words
