"""ctypes bindings for the native (C++) data-pipeline runtime.

The reference's native data path lived in the TF wheel's C++ runtime
(SURVEY.md §2.2); ours is authored in ``native/dtm.cpp`` and consumed here
via ctypes (no pybind11 in this environment).  The library is compiled
lazily with g++ on first use into ``native/build/`` under a name keyed on
the SOURCE'S CONTENT, so a stale or foreign binary is never loaded: a
checkout builds what its own ``dtm.cpp`` says.  Every entry point has a
numpy fallback, so the framework never *requires* a working toolchain —
but a build that was attempted and failed says so (a ``RuntimeWarning``
with the compiler's stderr), and ``status()`` reports which path you're on
and why.

Surface:
* :func:`gather` — parallel batch-assembly gather (out[i] = src[idx[i]]);
* :func:`render_affine` — the synthetic-dataset renderer, multithreaded and
  deterministic per (seed, sample) regardless of thread count;
* :class:`Prefetcher` — threaded, depth-bounded batch prefetch iterator
  (assembles batch b while batch b-1 trains).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings
from pathlib import Path

import numpy as np

_SRC = Path(__file__).resolve().parents[2] / "native" / "dtm.cpp"
_BUILD_DIR = _SRC.parent / "build"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False
_WHY_NOT: str | None = None  # why the numpy path is live, once _TRIED

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_f32p = ctypes.POINTER(ctypes.c_float)


def _so_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libdtm-{digest}.so"


def _compile() -> Path:
    """Build (or reuse) the library for THIS source; raises on failure."""
    so = _so_path()
    if so.exists():
        return so
    _BUILD_DIR.mkdir(exist_ok=True)
    tmp = so.with_suffix(f".{os.getpid()}.tmp")  # concurrent builders: atomic publish
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    finally:
        tmp.unlink(missing_ok=True)
    return so


def _load() -> ctypes.CDLL | None:
    global _LIB, _TRIED, _WHY_NOT
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("DTM_DISABLE_NATIVE"):
            _WHY_NOT = "DTM_DISABLE_NATIVE is set"
            return None
        try:
            lib = ctypes.CDLL(str(_compile()))
        except (OSError, subprocess.SubprocessError) as e:
            stderr = getattr(e, "stderr", b"") or b""
            _WHY_NOT = f"{e!r} {stderr.decode(errors='replace')[-800:]}".strip()
            warnings.warn(
                f"native data library unavailable, numpy path in use: "
                f"{_WHY_NOT}", RuntimeWarning, stacklevel=3)
            return None
        lib.dtm_gather.argtypes = [_u8p, _i32p, _u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
        lib.dtm_render_affine.argtypes = [
            _f32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            _i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_uint64, _u8p, ctypes.c_int32,
        ]
        lib.dtm_prefetch_create.argtypes = [
            _u8p, _i32p, ctypes.c_int64, ctypes.c_int64, _i32p,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ]
        lib.dtm_prefetch_create.restype = ctypes.c_void_p
        lib.dtm_prefetch_next.argtypes = [ctypes.c_void_p, _u8p, _i32p]
        lib.dtm_prefetch_next.restype = ctypes.c_int32
        lib.dtm_prefetch_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def available() -> bool:
    """Whether the C++ library compiled and loaded on this machine."""
    return _load() is not None


def status() -> dict:
    """Which data path is live and why: ``{"path": "native", "library":
    <file>}`` or ``{"path": "numpy", "reason": <why the build or load did
    not happen>}`` (attempts the build on first call)."""
    if _load() is not None:
        return {"path": "native", "library": str(_so_path())}
    return {"path": "numpy", "reason": _WHY_NOT}


def _ptr(a: np.ndarray, ty):
    return a.ctypes.data_as(ty)


def gather(src: np.ndarray, idx: np.ndarray, threads: int = 0) -> np.ndarray:
    """out[i] = src[idx[i]] over the leading axis, parallel in C++.

    Falls back to ``np.take`` without the library.
    """
    lib = _load()
    if lib is None:
        return np.take(src, idx, axis=0)
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, np.int32)
    out = np.empty((idx.shape[0],) + src.shape[1:], src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.dtm_gather(
        _ptr(src.view(np.uint8).reshape(src.shape[0], -1), _u8p),
        _ptr(idx, _i32p),
        _ptr(out.view(np.uint8).reshape(out.shape[0], -1), _u8p),
        idx.shape[0], row_bytes, threads,
    )
    return out


def render_affine(
    templates: np.ndarray,
    labels: np.ndarray,
    out_hw: tuple[int, int],
    scale_range: tuple[float, float],
    rot_range: float,
    shift_frac: float,
    noise_std: float,
    seed: int,
    threads: int = 0,
) -> np.ndarray | None:
    """C++ twin of synthetic.py's ``_render_affine`` (own RNG stream).

    templates (C, gh, gw[, ch]) float32 in [0,1] -> uint8 (N, H, W, ch).
    Returns None without the library (caller falls back to numpy).
    """
    lib = _load()
    if lib is None:
        return None
    if templates.ndim == 3:
        templates = templates[..., None]
    templates = np.ascontiguousarray(templates, np.float32)
    labels = np.ascontiguousarray(labels, np.int32)
    n_classes, gh, gw, ch = templates.shape
    h, w = out_hw
    out = np.empty((labels.shape[0], h, w, ch), np.uint8)
    lib.dtm_render_affine(
        _ptr(templates, _f32p), n_classes, gh, gw, ch,
        _ptr(labels, _i32p), labels.shape[0], h, w,
        scale_range[0], scale_range[1], rot_range, shift_frac, noise_std,
        np.uint64(seed), _ptr(out, _u8p), threads,
    )
    return out


class Prefetcher:
    """Iterate (images, labels) batches assembled by C++ worker threads.

    ``perm`` is the epoch's flat index order (n_batches * batch entries);
    batches come back in order, assembled ``depth`` ahead of the consumer.
    Without the library, iterates with numpy gathers instead.
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch: int,
        perm: np.ndarray,
        depth: int = 3,
        threads: int = 2,
    ):
        self._images = np.ascontiguousarray(images)
        self._labels = np.ascontiguousarray(labels, np.int32)
        self._perm = np.ascontiguousarray(perm, np.int32)
        self._batch = batch
        self._n_batches = len(self._perm) // batch
        self._img_shape = images.shape[1:]
        self._img_bytes = images.dtype.itemsize * int(np.prod(images.shape[1:], dtype=np.int64))
        self._lib = _load()
        self._handle = None
        # The C fast path copies ONE int32 label per sample; per-position
        # label arrays (causal LM: (N, S)) take the numpy path below, which
        # gathers label rows of any rank.
        if self._lib is not None and self._labels.ndim == 1:
            self._handle = self._lib.dtm_prefetch_create(
                _ptr(self._images.view(np.uint8).reshape(images.shape[0], -1), _u8p),
                _ptr(self._labels, _i32p),
                self._img_bytes, batch, _ptr(self._perm, _i32p),
                self._n_batches, depth, threads,
            )
        self._next_py = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._handle is not None:
            img = np.empty((self._batch,) + self._img_shape, self._images.dtype)
            lab = np.empty((self._batch,), np.int32)
            ok = self._lib.dtm_prefetch_next(
                self._handle,
                _ptr(img.view(np.uint8).reshape(self._batch, -1), _u8p),
                _ptr(lab, _i32p),
            )
            if not ok:
                raise StopIteration
            return img, lab
        b = self._next_py
        if b >= self._n_batches:
            raise StopIteration
        self._next_py += 1
        idx = self._perm[b * self._batch : (b + 1) * self._batch]
        return np.take(self._images, idx, axis=0), self._labels[idx]

    def close(self):
        if self._handle is not None:
            self._lib.dtm_prefetch_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
