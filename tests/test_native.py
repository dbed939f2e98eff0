"""Native C++ data pipeline: gather/renderer/prefetcher vs numpy truth.

These tests compile the library on first run (cached after, under a name
keyed on the source's content).  Without a working C++ toolchain the
bindings fall back to numpy with a warning; the fallback itself is
exercised by the disabled-library test.
"""

import numpy as np
import pytest

from distributed_tensorflow_ibm_mnist_tpu.data import native
from distributed_tensorflow_ibm_mnist_tpu.data.synthetic import (
    _DIGIT_GLYPHS,
    _glyphs_to_array,
    _make_split,
)

needs_native = pytest.mark.skipif(not native.available(), reason="no C++ toolchain")


@needs_native
def test_gather_matches_numpy():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 255, size=(500, 28, 28, 1), dtype=np.uint8)
    idx = rng.permutation(500)[:128].astype(np.int32)
    got = native.gather(src, idx, threads=4)
    np.testing.assert_array_equal(got, np.take(src, idx, axis=0))


@needs_native
def test_gather_float_rows():
    rng = np.random.default_rng(1)
    src = rng.normal(size=(100, 17)).astype(np.float32)
    idx = rng.integers(0, 100, size=64).astype(np.int32)
    np.testing.assert_array_equal(native.gather(src, idx), src[idx])


@needs_native
def test_render_deterministic_and_thread_independent():
    templates = _glyphs_to_array(_DIGIT_GLYPHS)
    labels = np.arange(40, dtype=np.int32) % 10
    kw = dict(
        out_hw=(28, 28), scale_range=(2.2, 3.4), rot_range=0.3,
        shift_frac=0.12, noise_std=0.18, seed=7,
    )
    a = native.render_affine(templates, labels, threads=1, **kw)
    b = native.render_affine(templates, labels, threads=8, **kw)
    np.testing.assert_array_equal(a, b)  # per-sample streams: thread-invariant
    c = native.render_affine(templates, labels, threads=4, **kw)
    np.testing.assert_array_equal(a, c)


@needs_native
def test_render_produces_learnable_digits():
    """Sanity on the rendered distribution: ink where expected, classes differ."""
    templates = _glyphs_to_array(_DIGIT_GLYPHS)
    labels = np.repeat(np.arange(10, dtype=np.int32), 20)
    imgs = native.render_affine(
        templates, labels, out_hw=(28, 28), scale_range=(2.2, 3.4),
        rot_range=0.3, shift_frac=0.12, noise_std=0.18, seed=0,
    )
    assert imgs.shape == (200, 28, 28, 1) and imgs.dtype == np.uint8
    ink = imgs.astype(np.float32).mean(axis=(1, 2, 3))
    assert 10.0 < ink.mean() < 120.0  # neither blank nor saturated
    # per-class mean images must be mutually distinguishable
    means = np.stack([imgs[labels == c].mean(axis=0).ravel() for c in range(10)])
    d = np.linalg.norm(means[:, None] - means[None, :], axis=-1)
    assert (d + np.eye(10) * 1e9).min() > 50.0


@needs_native
def test_make_split_native_backend():
    templates = _glyphs_to_array(_DIGIT_GLYPHS)
    kw = dict(
        out_hw=(28, 28), scale_range=(2.2, 3.4), rot_range=0.3,
        shift_frac=0.12, noise_std=0.18,
    )
    x, y = _make_split(templates, 64, seed=3, backend="native", **kw)
    x2, y2 = _make_split(templates, 64, seed=3, backend="native", **kw)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(y, y2)
    # same labels as the numpy backend (labels come from the shared stream)
    _, y_np = _make_split(templates, 64, seed=3, backend="numpy", **kw)
    np.testing.assert_array_equal(y, y_np)
    assert x.shape == (64, 28, 28, 1) and x.dtype == np.uint8


@needs_native
def test_prefetcher_matches_order():
    rng = np.random.default_rng(2)
    images = rng.integers(0, 255, size=(300, 8, 8, 1), dtype=np.uint8)
    labels = rng.integers(0, 10, size=300).astype(np.int32)
    perm = rng.permutation(300).astype(np.int32)[:256]
    batch = 32
    with native.Prefetcher(images, labels, batch, perm, depth=3, threads=3) as pf:
        got = list(pf)
    assert len(got) == 8
    for b, (img, lab) in enumerate(got):
        idx = perm[b * batch : (b + 1) * batch]
        np.testing.assert_array_equal(img, images[idx])
        np.testing.assert_array_equal(lab, labels[idx])


def test_fallback_without_native(monkeypatch):
    """With the library disabled, every entry point still works via numpy."""
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    rng = np.random.default_rng(3)
    src = rng.integers(0, 255, size=(50, 4), dtype=np.uint8)
    idx = np.arange(10, dtype=np.int32)
    np.testing.assert_array_equal(native.gather(src, idx), src[:10])
    assert native.render_affine(
        np.zeros((10, 7, 5), np.float32), idx, (28, 28), (2.0, 3.0), 0.3, 0.1, 0.1, 0
    ) is None
    labels = rng.integers(0, 10, size=50).astype(np.int32)
    perm = np.arange(48, dtype=np.int32)
    with native.Prefetcher(src, labels, 16, perm) as pf:
        got = list(pf)
    assert len(got) == 3
    np.testing.assert_array_equal(got[1][0], src[16:32])


def test_build_is_keyed_on_source_content_and_failure_is_loud(monkeypatch, tmp_path):
    """The loaded library's name carries the hash of native/dtm.cpp (a stale
    or foreign binary under another name is never picked up), and a build
    that fails says so instead of silently taking the numpy path."""
    import hashlib

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native._so_path().name == f"libdtm-{digest}.so"
    if native.available():
        assert native.status() == {
            "path": "native", "library": str(native._so_path())}

    bad = tmp_path / "dtm.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", bad)
    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path / "build")
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_WHY_NOT", None)
    monkeypatch.delenv("DTM_DISABLE_NATIVE", raising=False)
    with pytest.warns(RuntimeWarning, match="numpy path in use"):
        assert not native.available()
    st = native.status()
    assert st["path"] == "numpy" and st["reason"]
    assert not list((tmp_path / "build").glob("*.so"))
