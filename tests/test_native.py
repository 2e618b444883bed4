"""Native C++ gather library + prefetcher tests (NumPy-equivalence gate)."""

import numpy as np
import pytest

from simclr_tpu.data.prefetch import Prefetcher, prefetch
from simclr_tpu.native.lib import gather_rows, gather_rows2, native_available


class TestNativeGather:
    def test_library_builds(self):
        # g++ is in the image; the lazy build must succeed here
        assert native_available()

    @pytest.mark.parametrize("n_threads", [1, 4])
    def test_matches_numpy_take(self, n_threads):
        rng = np.random.default_rng(0)
        src = rng.integers(0, 256, size=(100, 32, 32, 3), dtype=np.uint8)
        idx = rng.permutation(100)[:37]
        np.testing.assert_array_equal(
            gather_rows(src, idx, n_threads=n_threads), src[idx]
        )

    def test_float_rows(self):
        rng = np.random.default_rng(1)
        src = rng.normal(size=(50, 17)).astype(np.float32)
        idx = rng.integers(0, 50, size=64)
        np.testing.assert_array_equal(gather_rows(src, idx), src[idx])

    def test_gather_rows2(self):
        rng = np.random.default_rng(2)
        images = rng.integers(0, 256, size=(64, 32, 32, 3), dtype=np.uint8)
        labels = rng.integers(0, 10, size=64).astype(np.int32)
        idx = rng.permutation(64)
        out_i, out_l = gather_rows2(images, labels, idx)
        np.testing.assert_array_equal(out_i, images[idx])
        np.testing.assert_array_equal(out_l, labels[idx])

    def test_empty_index(self):
        src = np.arange(12, dtype=np.uint8).reshape(3, 4)
        assert gather_rows(src, np.array([], dtype=np.int64)).shape == (0, 4)


@pytest.fixture
def fresh_build_dir(tmp_path, monkeypatch):
    """Point the loader at an empty build directory and forget what this
    process already loaded."""
    from simclr_tpu.native import lib

    so = str(tmp_path / "_build" / "libsimclr_gather.so")
    monkeypatch.setattr(lib, "_BUILD_DIR", str(tmp_path / "_build"))
    monkeypatch.setattr(lib, "_SO", so)
    monkeypatch.setattr(lib, "_SO_SOURCE_HASH", so + ".source-sha256")
    monkeypatch.setattr(lib, "_tried", False)
    monkeypatch.setattr(lib, "_lib", None)
    return lib


class TestNativeBuildProvenance:
    """Only tracked source decides what runs (``gather.cpp``'s sha256 is
    recorded next to the library it was built from)."""

    def test_build_records_the_source_hash(self, fresh_build_dir):
        lib = fresh_build_dir
        assert lib.native_available()
        with open(lib._SO_SOURCE_HASH) as f:
            assert f.read().strip() == lib._source_hash()

    @pytest.mark.parametrize("record", [None, "0" * 64])
    def test_stale_library_is_rebuilt_not_loaded(self, fresh_build_dir, record):
        """A ``.so`` with no record, or with the record of another source,
        is replaced. The planted file is not even a library: loading it as
        it is would fail."""
        lib = fresh_build_dir
        import os

        os.makedirs(lib._BUILD_DIR)
        with open(lib._SO, "wb") as f:
            f.write(b"left over from another checkout")
        if record is not None:
            with open(lib._SO_SOURCE_HASH, "w") as f:
                f.write(record + "\n")
        assert lib.native_available()
        src = np.arange(12, dtype=np.uint8).reshape(4, 3)
        np.testing.assert_array_equal(
            lib.gather_rows(src, np.array([2, 0])), src[[2, 0]]
        )
        with open(lib._SO_SOURCE_HASH) as f:
            assert f.read().strip() == lib._source_hash()

    def test_matching_record_skips_the_compiler(self, fresh_build_dir, monkeypatch):
        lib = fresh_build_dir
        assert lib.native_available()
        monkeypatch.setattr(lib, "_tried", False)
        monkeypatch.setattr(lib, "_lib", None)

        def no_compiler(*args, **kwargs):
            raise AssertionError("rebuilt a library whose source did not change")

        monkeypatch.setattr(lib.subprocess, "run", no_compiler)
        assert lib.native_available()

    def test_failed_build_warns_once_and_uses_numpy(self, fresh_build_dir, monkeypatch):
        import logging

        lib = fresh_build_dir

        def no_compiler(*args, **kwargs):
            raise FileNotFoundError("g++")

        monkeypatch.setattr(lib.subprocess, "run", no_compiler)
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        logger = logging.getLogger("simclr_tpu")
        logger.addHandler(handler)
        try:
            assert not lib.native_available()
            src = np.arange(12, dtype=np.uint8).reshape(4, 3)
            np.testing.assert_array_equal(
                lib.gather_rows(src, np.array([3, 1])), src[[3, 1]]
            )
            assert not lib.native_available()
        finally:
            logger.removeHandler(handler)
        warnings = [r for r in records if r.levelno == logging.WARNING]
        assert len(warnings) == 1
        assert "NumPy path" in warnings[0].getMessage()


class TestPrefetcher:
    def test_yields_all_in_order(self):
        items = list(prefetch(iter(range(10))))
        assert items == list(range(10))

    def test_propagates_worker_exception(self):
        def gen():
            yield 1
            raise RuntimeError("boom")

        it = prefetch(gen())
        assert next(it) == 1
        with pytest.raises(RuntimeError, match="boom"):
            for _ in it:
                pass

    def test_close_early(self):
        with Prefetcher(iter(range(1000)), depth=2) as it:
            assert next(it) == 0
        # close() returned without deadlock; thread is gone
        assert not it._thread.is_alive()

    def test_overlaps_with_pipeline(self):
        from simclr_tpu.data.cifar import synthetic_dataset
        from simclr_tpu.data.pipeline import EpochIterator

        ds = synthetic_dataset("cifar10", "train", size=64)
        it = EpochIterator(ds, global_batch=16, seed=0)
        batches = list(prefetch(it.batches(0)))
        assert len(batches) == 4
        assert batches[0]["image"].shape == (16, 32, 32, 3)

    def test_error_reaches_consumer_past_a_full_queue(self):
        # depth-1 queue already holding an item when the worker dies: the
        # termination sentinel is dropped on the full queue, and __next__
        # must fall back to the done flag to surface the error rather than
        # poll forever
        def gen():
            yield 1
            yield 2
            raise RuntimeError("late boom")

        it = prefetch(gen(), depth=1)
        got = []
        with pytest.raises(RuntimeError, match="late boom"):
            for item in it:
                got.append(item)
        assert got == [1, 2]  # batches produced before the failure are valid

    def test_close_returns_promptly_with_wedged_producer(self):
        import threading
        import time

        release = threading.Event()

        def gen():
            yield 0
            release.wait(timeout=30)  # a hung transfer, effectively
            yield 1

        it = prefetch(gen(), depth=1)
        assert next(it) == 0
        t0 = time.monotonic()
        it.close(timeout=0.5)
        assert time.monotonic() - t0 < 5  # bounded even though the producer hangs
        release.set()

    def test_close_idempotent_after_exhaustion(self):
        it = prefetch(iter(range(3)))
        assert list(it) == [0, 1, 2]
        it.close()
        it.close()
        assert not it._thread.is_alive()
