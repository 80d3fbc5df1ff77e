"""Loader for the native C++ runtime (``apex_tpu/csrc/apex_runtime.cpp``).

Mirrors the reference's two-tier install contract (SURVEY.md §1: "a
Python-only install must remain fully functional"): the .so is built on
first use with g++ if available; every entry point has a numpy fallback, and
``available`` reports which tier is active — the analog of
``multi_tensor_applier.available``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import List, Optional, Sequence

import numpy as np

_CSRC = os.path.join(os.path.dirname(__file__), "csrc")
_SO = os.path.join(_CSRC, "build", "libapex_tpu_runtime.so")
_ABI_VERSION = 2      # v2: synth_u8 + crop_flip_norm (ISSUE 3)
_lock = threading.Lock()
_lib = None
available = False


def _build() -> Optional[str]:
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    src = os.path.join(_CSRC, "apex_runtime.cpp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
           src, "-o", _SO]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return _SO
    except (OSError, subprocess.SubprocessError) as e:
        # The numpy tier is a supported install, but dropping to it must
        # not be silent: say once (``_load`` runs the build once per
        # process) what the compiler said.
        detail = getattr(e, "stderr", None)
        if isinstance(detail, bytes):
            detail = detail.decode(errors="replace")
        print(f"apex_tpu.native: build of {src} failed "
              f"({type(e).__name__}: {e}); using the numpy tier\n"
              f"{(detail or '').strip()}", file=sys.stderr)
        return None


def _load():
    global _lib, available
    with _lock:
        if _lib is not None or available is None:
            return _lib
        if os.environ.get("APEX_TPU_DISABLE_NATIVE"):
            # Force the Python tier (install-matrix / docker/run_matrix.sh
            # tiers 2 and 4): without this the lazy builder would simply
            # rebuild a deleted .so whenever g++ is present, making a
            # "no-native" tier silently native again.
            available = False
            _lib = False
            return None
        src = os.path.join(_CSRC, "apex_runtime.cpp")
        try:
            stale = (not os.path.exists(_SO)
                     or os.path.getmtime(_SO) < os.path.getmtime(src))
        except OSError:
            # Prebuilt .so shipped without the source: nothing to
            # compare against (or rebuild from) — trust the ABI check.
            stale = not os.path.exists(_SO)
        path = _build() if stale else _SO
        if path is None and os.path.exists(_SO):
            # mtime said stale but no compiler is available (prebuilt
            # .so shipped without g++; checkouts don't preserve mtimes):
            # trust the ABI-version check below to judge the existing
            # build rather than silently dropping to the numpy tier.
            path = _SO
        if path is None:
            available = False
            _lib = False
            return None
        try:
            lib = ctypes.CDLL(path)
            if lib.apex_runtime_abi_version() != _ABI_VERSION:
                # A stale build dir from an older checkout (mtime lies
                # across git checkouts): rebuild once, then give up.
                # Unlink first — rebuilding IN PLACE keeps the inode,
                # and dlopen dedups by (st_dev, st_ino), so a second
                # CDLL of the same path would return the stale handle.
                try:
                    os.remove(_SO)
                except OSError:
                    pass
                path = _build()
                lib = ctypes.CDLL(path) if path else None
                assert lib is not None \
                    and lib.apex_runtime_abi_version() == _ABI_VERSION
        except Exception:
            available = False
            _lib = False
            return None
        lib.apex_flatten.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int]
        lib.apex_unflatten.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int]
        lib.apex_u8_to_f32_nhwc.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int]
        lib.apex_synth_u8.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_int]
        lib.apex_crop_flip_norm_u8_f32.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int]
        _lib = lib
        available = True
        return lib


_DEFAULT_THREADS = max(1, (os.cpu_count() or 1) - 1)


def flatten(arrays: Sequence[np.ndarray], threads: int = _DEFAULT_THREADS
            ) -> np.ndarray:
    """Pack host arrays into one contiguous byte buffer (reference
    ``apex_C.flatten``, csrc/flatten_unflatten.cpp)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    sizes = np.array([a.nbytes for a in arrays], np.int64)
    out = np.empty(int(sizes.sum()), np.uint8)
    lib = _load()
    if lib:
        srcs = (ctypes.c_void_p * len(arrays))(
            *[a.ctypes.data for a in arrays])
        lib.apex_flatten(srcs, sizes.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)), len(arrays),
            out.ctypes.data_as(ctypes.c_void_p), threads)
    else:
        off = 0
        for a, n in zip(arrays, sizes):
            out[off:off + n] = a.view(np.uint8).reshape(-1)
            off += int(n)
    return out


def unflatten(flat: np.ndarray, like: Sequence[np.ndarray],
              threads: int = _DEFAULT_THREADS) -> List[np.ndarray]:
    """Split a flat byte buffer back into arrays shaped like ``like``
    (reference ``apex_C.unflatten``)."""
    flat = np.ascontiguousarray(flat.view(np.uint8).reshape(-1))
    outs = [np.empty(a.shape, a.dtype) for a in like]
    sizes = np.array([a.nbytes for a in outs], np.int64)
    if int(sizes.sum()) != flat.nbytes:
        raise ValueError(f"flat buffer has {flat.nbytes} bytes, "
                         f"targets need {int(sizes.sum())}")
    lib = _load()
    if lib:
        dsts = (ctypes.c_void_p * len(outs))(
            *[o.ctypes.data for o in outs])
        lib.apex_unflatten(flat.ctypes.data_as(ctypes.c_void_p),
                           sizes.ctypes.data_as(
                               ctypes.POINTER(ctypes.c_int64)),
                           len(outs), dsts, threads)
    else:
        off = 0
        for o, n in zip(outs, sizes):
            o.view(np.uint8).reshape(-1)[:] = flat[off:off + int(n)]
            off += int(n)
    return outs


def u8_to_f32_nhwc(images: np.ndarray, mean: Sequence[float],
                   std: Sequence[float],
                   threads: int = _DEFAULT_THREADS) -> np.ndarray:
    """Normalize a uint8 NHWC batch to float32: ``(x/255 - mean)/std`` —
    the input-pipeline decode epilogue (the reference's examples lean on
    DALI for this)."""
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    if mean.size != c or std.size != c:
        raise ValueError("mean/std length must equal channel count")
    out = np.empty((n, h, w, c), np.float32)
    lib = _load()
    if lib:
        lib.apex_u8_to_f32_nhwc(
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, h * w, c,
            mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), threads)
    else:
        out[:] = (images.astype(np.float32) / 255.0 - mean) / std
    return out


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 over a uint64 lattice — the numpy mirror of
    the C++ generator, bit-identical by construction."""
    z = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def synth_bytes(nbytes: int, seed: int,
                threads: int = _DEFAULT_THREADS) -> np.ndarray:
    """Counter-based pseudorandom byte stream: block ``i`` of 8 bytes is
    ``splitmix64(seed + i)``.  Native tier fills the buffer in parallel
    with zero GIL time; the numpy fallback computes the same lattice
    (both little-endian — asserted below, not assumed).  This is the
    synthetic-batch generator backing :func:`apex_tpu.data.
    synthetic_imagenet` (ISSUE 3: Python-side ``np.random`` generation
    was a measurable producer-side GIL burn)."""
    if nbytes < 0:
        raise ValueError(f"nbytes must be >= 0, got {nbytes}")
    import sys
    assert sys.byteorder == "little", \
        "synth_bytes assumes a little-endian host (the C++ tier memcpys " \
        "uint64 blocks); add a byteswap for big-endian targets"
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    out = np.empty(nbytes, np.uint8)
    lib = _load()
    if lib:
        lib.apex_synth_u8(
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            nbytes, ctypes.c_uint64(seed), threads)
    else:
        blocks = (nbytes + 7) // 8
        lattice = np.arange(blocks, dtype=np.uint64) + np.uint64(seed)
        with np.errstate(over="ignore"):
            words = _splitmix64(lattice)
        out[:] = words.view(np.uint8)[:nbytes]
    return out


def crop_flip_normalize(images: np.ndarray, out_size: int,
                        offsets: np.ndarray, flips: np.ndarray,
                        mean: Sequence[float], std: Sequence[float],
                        threads: int = _DEFAULT_THREADS) -> np.ndarray:
    """Fused augmentation epilogue: per-image ``out_size`` crop at
    ``offsets[i] = (oy, ox)``, horizontal flip where ``flips[i]``, and
    the ``(x/255 - mean)/std`` normalize — ONE pass over the output
    pixels (the reference delegates exactly this fusion to DALI).
    ``images`` is uint8 NHWC; returns float32 ``[n, out, out, c]``.
    Randomness is the CALLER's job (pass offsets/flips), so both tiers
    are deterministic and bit-comparable."""
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    oh = ow = int(out_size)
    if oh > h or ow > w:
        raise ValueError(f"crop {oh}x{ow} exceeds image {h}x{w}")
    offsets = np.ascontiguousarray(offsets, np.int32).reshape(n, 2)
    if (offsets[:, 0] < 0).any() or (offsets[:, 0] > h - oh).any() \
            or (offsets[:, 1] < 0).any() or (offsets[:, 1] > w - ow).any():
        raise ValueError("crop offsets out of bounds")
    flips = np.ascontiguousarray(flips, np.uint8).reshape(n)
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    if mean.size != c or std.size != c:
        raise ValueError("mean/std length must equal channel count")
    out = np.empty((n, oh, ow, c), np.float32)
    lib = _load()
    if lib:
        lib.apex_crop_flip_norm_u8_f32(
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, h, w, c, oh, ow,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            flips.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), threads)
    else:
        for i in range(n):
            oy, ox = int(offsets[i, 0]), int(offsets[i, 1])
            crop = images[i, oy:oy + oh, ox:ox + ow]
            if flips[i]:
                crop = crop[:, ::-1]
            out[i] = (crop.astype(np.float32) / 255.0 - mean) / std
    return out
