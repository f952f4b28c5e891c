"""Tests for the native data-pipeline library (native/src/data_native.cpp
via data/native.py): the Feistel epoch permutation and the threaded host
window gather, plus C++ <-> numpy fallback parity."""

import numpy as np
import pytest

from differential_transformer_replication_tpu.data import native
from differential_transformer_replication_tpu.data.native import (
    EpochPermutation,
    _permute_np,
    gather_windows,
    native_available,
    permute_indices,
)


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1000, 12_345])
def test_permutation_is_bijective(n):
    out = permute_indices(n, seed=42, start=0, count=n)
    assert sorted(out.tolist()) == list(range(n))


def test_permutation_windows_compose():
    """Streaming the permutation in chunks equals taking it whole."""
    n = 5000
    whole = permute_indices(n, seed=7, start=0, count=n)
    parts = np.concatenate(
        [permute_indices(n, seed=7, start=s, count=1000) for s in range(0, n, 1000)]
    )
    np.testing.assert_array_equal(parts, whole)


def test_different_seeds_differ():
    n = 4096
    a = permute_indices(n, seed=1, start=0, count=n)
    b = permute_indices(n, seed=2, start=0, count=n)
    assert not np.array_equal(a, b)


@pytest.mark.skipif(not native_available(), reason="no C++ toolchain")
@pytest.mark.parametrize("n", [3, 257, 10_000])
def test_cpp_matches_numpy(n):
    """The ctypes path and the numpy fallback implement the identical
    bijection, so behavior cannot depend on toolchain availability."""
    got = permute_indices(n, seed=99, start=0, count=n)  # C++ path
    ref = _permute_np(n, seed=99, start=0, count=n)
    np.testing.assert_array_equal(got, ref)


def test_gather_windows_semantics():
    tokens = np.arange(100, dtype=np.int32)
    offs = np.array([0, 5, 90], np.int64)
    out = gather_windows(tokens, offs, block=8)
    np.testing.assert_array_equal(out["x"][0], np.arange(8))
    np.testing.assert_array_equal(out["y"][0], np.arange(1, 9))
    np.testing.assert_array_equal(out["x"][2], np.arange(90, 98))
    np.testing.assert_array_equal(out["y"][2], np.arange(91, 99))


def test_gather_windows_bounds_check():
    tokens = np.arange(20, dtype=np.int32)
    with pytest.raises(ValueError):
        gather_windows(tokens, np.array([15], np.int64), block=8)


def test_epoch_permutation_exact_epochs():
    """Every index exactly once per epoch; epochs reshuffle; streaming
    across an epoch boundary works."""
    n = 103
    p = EpochPermutation(n, seed=5)
    first = p.take(n)
    assert sorted(first.tolist()) == list(range(n))
    assert p.epoch == 1 and p.cursor == 0
    # crossing the boundary: 2nd epoch's head differs from the 1st's
    second = p.take(n)
    assert sorted(second.tolist()) == list(range(n))
    assert not np.array_equal(first, second)
    # uneven take spanning epochs
    p2 = EpochPermutation(n, seed=5)
    chunks = np.concatenate([p2.take(40) for _ in range(6)])  # 240 = 2n + 34
    assert sorted(chunks[:n].tolist()) == list(range(n))
    assert sorted(chunks[n : 2 * n].tolist()) == list(range(n))
    np.testing.assert_array_equal(chunks[:n], first)


def test_native_reports_availability():
    # in this image g++ is baked in, so the native path should build;
    # if it ever can't, the numpy fallback keeps everything above passing
    assert isinstance(native_available(), bool)


def test_built_library_is_keyed_by_source_hash(monkeypatch, tmp_path):
    """The .so name carries the hash of the C++ source, so a copied tree
    (mtimes out of order) never loads a binary of another version: an
    edited source resolves to a different, not-yet-built path."""
    import hashlib

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native._lib_path().name == f"libdata_native.{digest}.so"
    if native_available():
        assert native._lib_path().exists()
    edited = tmp_path / "data_native.cpp"
    edited.write_bytes(native._SRC.read_bytes() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", edited)
    assert native._lib_path().name != f"libdata_native.{digest}.so"
    assert not native._lib_path().exists()
