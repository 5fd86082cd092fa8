"""Tests for the native (C++) IDX loader against the numpy reference."""

import os

import numpy as np
import pytest

from dtf_tpu.data.native import NativeIdxData, native_available
from tests.test_data import _write_idx

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="no C++ toolchain")


@pytest.fixture
def idx_files(tmp_path):
    r = np.random.RandomState(7)
    images = r.randint(0, 256, (40, 28, 28)).astype(np.uint8)
    labels = r.randint(0, 10, (40,)).astype(np.uint8)
    ip = os.path.join(str(tmp_path), "imgs")
    lp = os.path.join(str(tmp_path), "labels")
    _write_idx(ip, images)
    _write_idx(lp, labels)
    return ip, lp, images, labels


def test_batches_match_source(idx_files):
    ip, lp, images, labels = idx_files
    ref = images.reshape(40, -1).astype(np.float32) * np.float32(1.0 / 255.0)
    loader = NativeIdxData(ip, lp, 8, seed=3)
    seen = {}
    for _ in range(5):  # one full epoch
        b = loader.next_batch()
        assert b["image"].shape == (8, 784)
        for img, lab in zip(b["image"], b["label"]):
            # identify the source row by exact content
            matches = np.where((ref == img).all(-1))[0]
            assert len(matches) >= 1
            assert labels[matches[0]] == lab
            seen[matches[0]] = seen.get(matches[0], 0) + 1
    # a full epoch visits every item exactly once
    assert sorted(seen) == list(range(40))
    assert all(v == 1 for v in seen.values())
    loader.close()


def test_deterministic_same_seed(idx_files):
    ip, lp, *_ = idx_files
    a = NativeIdxData(ip, lp, 8, seed=5)
    b = NativeIdxData(ip, lp, 8, seed=5)
    for _ in range(3):
        ba, bb = a.next_batch(), b.next_batch()
        np.testing.assert_array_equal(ba["image"], bb["image"])
        np.testing.assert_array_equal(ba["label"], bb["label"])
    a.close(); b.close()


def test_seeds_differ(idx_files):
    ip, lp, *_ = idx_files
    a = NativeIdxData(ip, lp, 8, seed=1)
    b = NativeIdxData(ip, lp, 8, seed=2)
    assert not np.array_equal(a.next_batch()["label"],
                              b.next_batch()["label"])
    a.close(); b.close()


def test_host_shards_disjoint(idx_files):
    ip, lp, images, _ = idx_files
    ref = images.reshape(40, -1).astype(np.float32) * np.float32(1.0 / 255.0)
    h0 = NativeIdxData(ip, lp, 8, seed=4, host_index=0, host_count=2)
    h1 = NativeIdxData(ip, lp, 8, seed=4, host_index=1, host_count=2)
    # collect one epoch (20 items per host = 2.5 local batches of 8 → use 2)
    rows = {0: set(), 1: set()}
    for host, loader in ((0, h0), (1, h1)):
        for _ in range(2):
            for img in loader.next_batch()["image"]:
                idx = np.where((ref == img).all(-1))[0][0]
                rows[host].add(int(idx))
    assert not (rows[0] & rows[1])
    h0.close(); h1.close()


def test_rejects_bad_input(tmp_path, idx_files):
    ip, lp, *_ = idx_files
    with pytest.raises(ValueError):
        NativeIdxData(ip, lp, 64, seed=0)  # batch > items/host
    bad = os.path.join(str(tmp_path), "nope")
    with pytest.raises(ValueError):
        NativeIdxData(bad, lp, 8)
    with pytest.raises(ValueError):
        NativeIdxData(ip, ip, 8)  # multi-dim file as labels (item_size != 1)


def test_use_after_close_raises(idx_files):
    ip, lp, *_ = idx_files
    loader = NativeIdxData(ip, lp, 8)
    loader.next_batch()
    loader.close()
    with pytest.raises(RuntimeError, match="close"):
        loader.next_batch()


def test_library_builds_unless_newer_than_source_and_makefile(
        tmp_path, monkeypatch):
    """The library is not committed and a copied checkout keeps no mtimes:
    build when it is absent, or not strictly newer than BOTH the source
    and the Makefile."""
    from dtf_tpu.data import native

    for name in ("dtfio.cpp", "Makefile"):
        (tmp_path / name).write_text("x")
        os.utime(tmp_path / name, (1000, 1000))
    so = tmp_path / "libdtfio.so"
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    assert native._stale()                       # absent
    so.write_text("x")
    os.utime(so, (1000, 1000))
    assert native._stale()                       # a copy: all one mtime
    os.utime(so, (2000, 2000))
    assert not native._stale()
    os.utime(tmp_path / "Makefile", (3000, 3000))
    assert native._stale()                       # flags changed
