"""ctypes bindings for the native dataio library (with numpy fallback).

The reference bound native code via ctypes wrappers (opencl4py/cuda4py —
SURVEY §2.4); same pattern here for the host data path: ``libdataio.so`` is
built from ``dataio.cpp`` on first use (g++, no dependencies) and loaded
with ctypes.  Every entry point has a numpy fallback, so the package works
unbuilt — ``available()`` says which path is live, and the env var
``VELES_TPU_NO_NATIVE=1`` forces the fallback (tests cover both).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libdataio.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _build():
    source = os.path.join(_HERE, "dataio.cpp")
    # compile to a temp name and rename into place: concurrent processes
    # (multi-process DP workers) must never CDLL a half-written file
    tmp = "%s.%d.tmp" % (_LIB_PATH, os.getpid())
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", tmp, source]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _LIB_PATH)


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("VELES_TPU_NO_NATIVE"):
            return None
        try:
            if not os.path.exists(_LIB_PATH) or (
                    os.path.getmtime(_LIB_PATH) <
                    os.path.getmtime(os.path.join(_HERE, "dataio.cpp"))):
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
            i64, f32 = ctypes.c_int64, ctypes.c_float
            ptr = ctypes.POINTER
            lib.gather_u8_to_f32.argtypes = [
                ptr(ctypes.c_uint8), ptr(ctypes.c_int32), i64, i64, f32,
                f32, ptr(ctypes.c_float)]
            lib.gather_f32.argtypes = [
                ptr(ctypes.c_float), ptr(ctypes.c_int32), i64, i64, f32,
                f32, ptr(ctypes.c_float)]
            lib.subtract_mean.argtypes = [
                ptr(ctypes.c_float), ptr(ctypes.c_float), i64, i64]
            lib.gather_i32.argtypes = [
                ptr(ctypes.c_int32), ptr(ctypes.c_int32), i64,
                ptr(ctypes.c_int32)]
            lib.dataio_abi_version.restype = ctypes.c_int
            if lib.dataio_abi_version() != 1:
                return None
        except (OSError, subprocess.CalledProcessError,
                AttributeError) as exc:
            # missing compiler, corrupt/stale .so (absent symbol) — the
            # numpy fallback takes over, and says so
            import logging
            logging.getLogger("native").warning(
                "libdataio.so unavailable (%r): the loader hot path runs "
                "the numpy gather", exc)
            return None
        _lib = lib
        return _lib


def find_pjrt_plugin():
    """Path of the installed libtpu PJRT plugin .so, or None — the ONE
    discovery bench.py's ``native`` config and the artifact-runner tests
    share."""
    import importlib.util
    spec = importlib.util.find_spec("libtpu")
    for root in (spec.submodule_search_locations or ()) if spec else ():
        path = os.path.join(root, "libtpu.so")
        if os.path.exists(path):
            return path
    return None


def available():
    """True when the native library is loaded (builds it on first call)."""
    return _load() is not None


def _as_ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def _numpy_gather(src, indices, scale, offset, out):
    """Fallback: src[indices] copies only the minibatch rows, so it is
    safe for strided/memmapped sources of any size."""
    numpy.multiply(src[indices], scale, out=out, casting="unsafe")
    if offset:
        out += offset
    return out


def gather_convert(src, indices, scale=1.0, offset=0.0, out=None):
    """out[i] = float32(src[indices[i]]) * scale + offset.

    src: (n, ...) uint8 or float32 array/memmap (C-contiguous rows);
    returns (len(indices), ...) float32.  The loader hot path.
    """
    indices = numpy.ascontiguousarray(indices, numpy.int32)
    sample_shape = src.shape[1:]
    sample_elems = int(numpy.prod(sample_shape)) if sample_shape else 1
    if out is None:
        out = numpy.empty((len(indices),) + sample_shape, numpy.float32)
    lib = _load()
    if lib is None or not src.flags.c_contiguous or \
            src.dtype not in (numpy.uint8, numpy.float32):
        # no library; or a strided view the kernel cannot index (it reads
        # rows at idx * sample_elems) — never ascontiguousarray a whole
        # ImageNet-scale memmap just to gather a minibatch from it
        return _numpy_gather(src, indices, scale, offset, out)
    if src.dtype == numpy.uint8:
        lib.gather_u8_to_f32(
            _as_ptr(src, ctypes.c_uint8), _as_ptr(indices, ctypes.c_int32),
            len(indices), sample_elems, scale, offset,
            _as_ptr(out, ctypes.c_float))
    else:
        lib.gather_f32(
            _as_ptr(src, ctypes.c_float), _as_ptr(indices, ctypes.c_int32),
            len(indices), sample_elems, scale, offset,
            _as_ptr(out, ctypes.c_float))
    return out


def gather_labels(src, indices, out=None):
    """int32 label gather."""
    indices = numpy.ascontiguousarray(indices, numpy.int32)
    if out is None:
        out = numpy.empty(len(indices), numpy.int32)
    lib = _load()
    if lib is None or src.dtype != numpy.int32:
        out[...] = src[indices]
        return out
    src = numpy.ascontiguousarray(src, numpy.int32)
    lib.gather_i32(_as_ptr(src, ctypes.c_int32),
                   _as_ptr(indices, ctypes.c_int32), len(indices),
                   _as_ptr(out, ctypes.c_int32))
    return out


def subtract_mean(batch, mean):
    """In-place batch -= mean (row-parallel when native).

    The native kernel requires a full sample-shaped mean; broadcastable
    means (e.g. per-channel (3,)) take the numpy path so both paths keep
    numpy's broadcasting semantics.
    """
    lib = _load()
    batch = numpy.ascontiguousarray(batch, numpy.float32)
    mean = numpy.ascontiguousarray(mean, numpy.float32)
    elems = int(numpy.prod(batch.shape[1:]))
    if lib is None or mean.size != elems:
        batch -= mean
        return batch
    lib.subtract_mean(_as_ptr(batch, ctypes.c_float),
                      _as_ptr(mean, ctypes.c_float), len(batch), elems)
    return batch
