"""ctypes bindings for the native decode plane (``pt_decode.cc``).

The shared library is compiled lazily on first import (g++ -O3, linked
against the system libjpeg/zlib) and cached next to the source; a stale or
failed build degrades gracefully — callers check :func:`get_lib` for ``None``
and fall back to the pure-python/cv2 codec paths, so the framework never
hard-requires the native component (same posture as the reference, whose
native speed all comes from optional third-party wheels — SURVEY.md §2.6).

Set ``PETASTORM_TPU_NO_NATIVE=1`` to disable the native path entirely.
"""

import ctypes
import logging
import os
import subprocess
from petastorm_tpu.utils.locks import make_lock

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, 'pt_decode.cc')
_SO = os.path.join(_HERE, 'libpt_decode.so')

_lock = make_lock('native._lock')
_lib = None
_tried = False
_force_disabled = False


import contextlib


@contextlib.contextmanager
def disabled():
    """Force the pure-python/cv2 fallback paths while the context is active.

    Unlike ``PETASTORM_TPU_NO_NATIVE`` (checked once, at first load), this
    works after the library has already been loaded — benchmarks use it to
    run an honest no-native baseline leg in the same process."""
    global _force_disabled
    prev = _force_disabled
    _force_disabled = True
    try:
        yield
    finally:
        _force_disabled = prev


def build():
    """Compile ``libpt_decode.so`` from ``pt_decode.cc`` now, replacing any
    binary already there; raises when the compiler fails.  (:func:`get_lib`
    builds only a missing or outdated binary and degrades on failure.)"""
    # Compile to a unique temp path and rename into place: os.rename is
    # atomic, so concurrent processes (ZeroMQ pool workers on a fresh
    # checkout) never dlopen a partially written ELF.
    tmp = '%s.%d.tmp' % (_SO, os.getpid())
    cmd = ['g++', '-O3', '-shared', '-fPIC', '-std=c++17',
           '-o', tmp, _SRC, '-ljpeg', '-lpng', '-lz']
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            raise RuntimeError('native build failed: %s' % proc.stderr[-2000:])
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):  # compile failure or timeout
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _load():
    lib = ctypes.CDLL(_SO)
    lib.pt_jpeg_decode_batch.restype = ctypes.c_int
    lib.pt_jpeg_decode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.pt_png_decode_batch.restype = ctypes.c_int
    lib.pt_png_decode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.pt_jpeg_decode_resize_batch.restype = ctypes.c_int
    lib.pt_jpeg_decode_resize_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.pt_png_decode_resize_batch.restype = ctypes.c_int
    lib.pt_png_decode_resize_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.pt_zlib_npy_decompress_batch.restype = ctypes.c_int
    lib.pt_zlib_npy_decompress_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t]
    lib.pt_npy_copy_batch.restype = ctypes.c_int
    lib.pt_npy_copy_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t]
    return lib


def get_lib():
    """The loaded native library, or None if unavailable/disabled."""
    global _lib, _tried
    if _force_disabled:
        return None
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        if os.environ.get('PETASTORM_TPU_NO_NATIVE'):
            _tried = True
            return None
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                build()
            _lib = _load()
        except Exception as e:  # noqa: BLE001 — any failure means "no native"
            logger.warning('Native decode library unavailable (%s); '
                           'falling back to cv2/python decode', e)
            _lib = None
        _tried = True
        return _lib


def _as_ptr_arrays(cells):
    """list[bytes] -> (char** array, size_t* array) borrowing the bytes."""
    n = len(cells)
    ptrs = (ctypes.c_char_p * n)(*cells)
    lens = (ctypes.c_size_t * n)(*[len(c) for c in cells])
    return ptrs, lens


def _arrow_ptr_arrays(column):
    """pyarrow binary (Chunked)Array -> (char**, size_t*, keepalive), borrowing
    the arrow buffers directly — no per-cell ``bytes`` copies, the marshalling
    win the ``to_pylist`` path can't have.  None when unsupported (nulls,
    non-binary type)."""
    import numpy as np
    import pyarrow as pa

    chunks = column.chunks if isinstance(column, pa.ChunkedArray) else [column]
    ptr_parts, len_parts = [], []
    for chunk in chunks:
        if chunk.null_count:
            return None
        if pa.types.is_binary(chunk.type):
            off_dtype = np.int32
        elif pa.types.is_large_binary(chunk.type):
            off_dtype = np.int64
        else:
            return None
        validity, offsets_buf, data_buf = chunk.buffers()
        # A sliced chunk shares its parent's buffers; chunk.offset shifts the
        # window into the offsets vector.
        offs = np.frombuffer(
            offsets_buf, dtype=off_dtype, count=len(chunk) + 1,
            offset=chunk.offset * np.dtype(off_dtype).itemsize).astype(np.uint64)
        ptr_parts.append(data_buf.address + offs[:-1])
        len_parts.append(np.diff(offs))
    ptrs = np.ascontiguousarray(np.concatenate(ptr_parts))
    lens = np.ascontiguousarray(np.concatenate(len_parts))
    return (ptrs.ctypes.data_as(ctypes.POINTER(ctypes.c_char_p)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_size_t)),
            (ptrs, lens, chunks))


def _marshal_cells(cells, expected_n=None):
    """Cells (list[bytes] OR pyarrow binary column) -> (char**, size_t*, n,
    keepalive); None if this cell container can't go native.

    ``expected_n``: the destination batch's row count — a cell count that
    differs must NOT reach the C loop (it would memcpy past the end of
    dst); mismatches return None so callers take the python fallback."""
    if expected_n is not None and len(cells) != expected_n:
        return None
    if isinstance(cells, (list, tuple)):
        if any(c is None for c in cells):
            return None
        ptrs, lens = _as_ptr_arrays(cells)
        return ptrs, lens, len(cells), cells
    try:
        import pyarrow as pa
        if isinstance(cells, (pa.Array, pa.ChunkedArray)):
            marshalled = _arrow_ptr_arrays(cells)
            if marshalled is None:
                return None
            ptrs, lens, keep = marshalled
            return ptrs, lens, len(cells), keep
    except ImportError:
        pass
    return None


def jpeg_decode_batch(cells, dst):
    """Decode list[bytes] JPEGs into a (N, H, W, 3)/(N, H, W) uint8 array.

    Returns True when the whole batch was decoded natively; False means the
    caller must use the fallback path (library missing, or some cell failed /
    had unexpected dimensions — dst contents are then undefined).
    """
    lib = get_lib()
    if lib is None or dst.dtype.kind != 'u' or dst.itemsize != 1 \
            or not dst.flags['C_CONTIGUOUS']:
        return False
    if dst.ndim == 4 and dst.shape[3] in (1, 3):
        h, w, c = dst.shape[1], dst.shape[2], dst.shape[3]
    elif dst.ndim == 3:
        h, w, c = dst.shape[1], dst.shape[2], 1
    else:
        return False
    marshalled = _marshal_cells(cells, expected_n=len(dst))
    if marshalled is None:
        return False
    ptrs, lens, n, keep = marshalled
    rc = lib.pt_jpeg_decode_batch(ptrs, lens, n,
                                  dst.ctypes.data_as(ctypes.c_void_p), h, w, c)
    del keep
    return rc == 0


def jpeg_decode_resize_batch(cells, dst):
    """Fused decode+resize: JPEGs of ANY source size -> the (N, H, W, 3) /
    (N, H, W) uint8 batch, decoded at the coarsest DCT scale covering
    (H, W) and bilinear-resampled to exactly (H, W).

    Sampling grid matches cv2.resize INTER_LINEAR (half-pixel centers).
    Accuracy vs the cv2 decode+resize fallback: a couple of LSB when the
    source decodes full-size (<=2x reductions, upscales, same-size); for
    >=4x reductions the DCT-scaled decode (what makes huge sources cheap)
    is anti-aliased where INTER_LINEAR aliases, so textured content
    diverges by tens of LSB — a documented quality difference, not noise.
    Same True/False contract as :func:`jpeg_decode_batch`.
    """
    lib = get_lib()
    if lib is None or dst.dtype.kind != 'u' or dst.itemsize != 1 \
            or not dst.flags['C_CONTIGUOUS']:
        return False
    if dst.ndim == 4 and dst.shape[3] in (1, 3):
        h, w, c = dst.shape[1], dst.shape[2], dst.shape[3]
    elif dst.ndim == 3:
        h, w, c = dst.shape[1], dst.shape[2], 1
    else:
        return False
    marshalled = _marshal_cells(cells, expected_n=len(dst))
    if marshalled is None:
        return False
    ptrs, lens, n, keep = marshalled
    rc = lib.pt_jpeg_decode_resize_batch(
        ptrs, lens, n, dst.ctypes.data_as(ctypes.c_void_p), h, w, c)
    del keep
    return rc == 0


def png_decode_resize_batch(cells, dst):
    """PNG sibling of :func:`jpeg_decode_resize_batch`: full decode (no
    scaled decode exists for PNG) + the same fixed-point bilinear into the
    (N, H, W, 3)/(N, H, W) batch — keeps PNG columns on the fused
    zero-per-row columnar path.  Same contract and same 8-bit/no-alpha
    rejections as :func:`png_decode_batch`."""
    lib = get_lib()
    if lib is None or dst.dtype.kind != 'u' or dst.itemsize != 1 \
            or not dst.flags['C_CONTIGUOUS']:
        return False
    if dst.ndim == 4 and dst.shape[3] in (1, 3):
        h, w, c = dst.shape[1], dst.shape[2], dst.shape[3]
    elif dst.ndim == 3:
        h, w, c = dst.shape[1], dst.shape[2], 1
    else:
        return False
    marshalled = _marshal_cells(cells, expected_n=len(dst))
    if marshalled is None:
        return False
    ptrs, lens, n, keep = marshalled
    rc = lib.pt_png_decode_resize_batch(
        ptrs, lens, n, dst.ctypes.data_as(ctypes.c_void_p), h, w, c)
    del keep
    return rc == 0


def png_decode_batch(cells, dst):
    """Decode list[bytes] 8-bit PNGs into a (N, H, W, 3)/(N, H, W[, 1]) uint8
    array.  Same contract as :func:`jpeg_decode_batch`: True = whole batch
    decoded natively; False = fall back (16-bit sources, channel mismatch,
    and anything else the C side rejects)."""
    lib = get_lib()
    if lib is None or dst.dtype.kind != 'u' or dst.itemsize != 1 \
            or not dst.flags['C_CONTIGUOUS']:
        return False
    if dst.ndim == 4 and dst.shape[3] in (1, 3):
        h, w, c = dst.shape[1], dst.shape[2], dst.shape[3]
    elif dst.ndim == 3:
        h, w, c = dst.shape[1], dst.shape[2], 1
    else:
        return False
    marshalled = _marshal_cells(cells, expected_n=len(dst))
    if marshalled is None:
        return False
    ptrs, lens, n, keep = marshalled
    rc = lib.pt_png_decode_batch(ptrs, lens, n,
                                 dst.ctypes.data_as(ctypes.c_void_p), h, w, c)
    del keep
    return rc == 0


def _npy_batch_call(fn_name, cells, dst):
    """Shared driver for the .npy column fast paths: render the exact
    header prefix np.save emits for dst's dtype/shape (np.lib.format's
    key order is fixed, so prefix match is exact), marshal the cells,
    and run one GIL-free C call over the whole column.  Fortran-ordered /
    reshaped / foreign-dtype cells are rejected natively and handled by
    the caller's ``np.load`` fallback.  True on full success."""
    lib = get_lib()
    if lib is None or not dst.flags['C_CONTIGUOUS'] or dst.dtype.hasobject:
        return False
    cell_bytes = dst[0].nbytes if len(dst) else 0
    if cell_bytes == 0:
        return False
    expected = "{'descr': %r, 'fortran_order': False, 'shape': %r," \
        % (dst.dtype.str, tuple(dst.shape[1:]))
    expected = expected.encode('latin1')
    marshalled = _marshal_cells(cells, expected_n=len(dst))
    if marshalled is None:
        return False
    ptrs, lens, n, keep = marshalled
    rc = getattr(lib, fn_name)(
        ptrs, lens, n, dst.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_size_t(cell_bytes), expected, ctypes.c_size_t(len(expected)))
    del keep
    return rc == 0


def zlib_npy_decompress_batch(cells, dst):
    """Inflate+unpack list[bytes] zlib(.npy) cells into a (N, ...) array
    (CompressedNdarrayCodec column); see :func:`_npy_batch_call`."""
    return _npy_batch_call('pt_zlib_npy_decompress_batch', cells, dst)


def npy_copy_batch(cells, dst):
    """Validate+copy list[bytes] raw .npy cells into a (N, ...) array
    (NdarrayCodec column — the pre-decoded-tensor delivery plane): one
    header check + memcpy per cell, whole column per GIL-free call,
    replacing a python ``np.load`` per cell; see :func:`_npy_batch_call`."""
    return _npy_batch_call('pt_npy_copy_batch', cells, dst)
