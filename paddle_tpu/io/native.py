"""ctypes bindings for the native host data-feed library.

ref: §2.14 #30 — the reference's C++ data_feed/data_set/data_loader core.
The .so is built on first use with the baked-in g++ (pybind11 is not in
this image; plain C ABI + ctypes instead) under the framework's cache root
(``compilecache.cache_root()/native``), keyed on a content hash of the
source — never committed, never stale after a clone, and safe across
machines (no -march=native). Every entry point has a numpy fallback so the
framework works without a compiler; a failed build says so once on stderr.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np

__all__ = [
    "available", "collate_images_u8_nchw", "gather_rows_f32",
    "pack_tokens",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "..", "csrc", "datafeed.cc")
_lock = threading.Lock()
_lib = None
_build_failed = False


def _cache_dir():
    from ..compilecache import cache_root

    base = os.path.join(cache_root(), "native")
    os.makedirs(base, exist_ok=True)
    return base


def _load():
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            with open(_SRC, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            so = os.path.join(_cache_dir(), f"libdatafeed-{digest}.so")
            if not os.path.exists(so):
                # build to a temp name then rename: atomic for concurrent
                # first-use from several processes
                fd, tmp = tempfile.mkstemp(
                    suffix=".so", dir=_cache_dir()
                )
                os.close(fd)
                try:
                    subprocess.run(
                        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                         _SRC, "-o", tmp, "-lpthread"],
                        check=True, capture_output=True,
                    )
                    os.chmod(tmp, 0o644)
                    os.replace(tmp, so)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(so)
            lib.ptpu_collate_images_u8_nchw.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int,
            ]
            lib.ptpu_gather_rows_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
            ]
            lib.ptpu_pack_tokens_i32.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p,
            ]
            _lib = lib
        except (OSError, subprocess.CalledProcessError) as e:
            _build_failed = True
            detail = getattr(e, "stderr", b"") or b""
            sys.stderr.write(
                "[io.native] libdatafeed did not build or load "
                f"({type(e).__name__}: {e}); the numpy paths are used "
                f"instead. {detail.decode(errors='replace')[-400:]}\n"
            )
    return _lib


def available() -> bool:
    return _load() is not None


def collate_images_u8_nchw(images, indices, mean, std, threads=4):
    """images: [N, H, W, C] uint8 contiguous; indices: int batch index
    list; returns float32 [B, C, H, W] normalized batch."""
    images = np.ascontiguousarray(images)
    idx = np.ascontiguousarray(np.asarray(indices, np.int64))
    b = len(idx)
    n, h, w, c = images.shape
    mean = np.ascontiguousarray(np.asarray(mean, np.float32))
    std = np.ascontiguousarray(np.asarray(std, np.float32))
    lib = _load()
    if lib is None:
        batch = images[idx].astype(np.float32) / 255.0
        batch = (batch - mean.reshape(1, 1, 1, -1)) / std.reshape(1, 1, 1, -1)
        return np.ascontiguousarray(batch.transpose(0, 3, 1, 2))
    out = np.empty((b, c, h, w), np.float32)
    lib.ptpu_collate_images_u8_nchw(
        images.ctypes.data, idx.ctypes.data, b, h, w, c,
        mean.ctypes.data, std.ctypes.data, out.ctypes.data, threads,
    )
    return out


def gather_rows_f32(matrix, indices, threads=4):
    """matrix: [N, ...] float32; returns [B, ...] gathered batch."""
    matrix = np.ascontiguousarray(matrix, np.float32)
    idx = np.ascontiguousarray(np.asarray(indices, np.int64))
    lib = _load()
    if lib is None:
        return matrix[idx].copy()
    row = int(np.prod(matrix.shape[1:])) if matrix.ndim > 1 else 1
    out = np.empty((len(idx),) + matrix.shape[1:], np.float32)
    lib.ptpu_gather_rows_f32(
        matrix.ctypes.data, idx.ctypes.data, len(idx), row,
        out.ctypes.data, threads,
    )
    return out


def pack_tokens(corpus, starts, seq_len, pad_id=0):
    """corpus: int32 token stream; starts: per-sample start offsets;
    returns int32 [B, seq_len] (the LLM pretraining feed)."""
    corpus = np.ascontiguousarray(np.asarray(corpus, np.int32))
    starts = np.ascontiguousarray(np.asarray(starts, np.int64))
    lib = _load()
    if lib is None:
        out = np.full((len(starts), seq_len), pad_id, np.int32)
        for i, s in enumerate(starts):
            chunk = corpus[s : s + seq_len]
            out[i, : len(chunk)] = chunk
        return out
    out = np.empty((len(starts), seq_len), np.int32)
    lib.ptpu_pack_tokens_i32(
        corpus.ctypes.data, len(corpus), starts.ctypes.data,
        len(starts), seq_len, pad_id, out.ctypes.data,
    )
    return out
