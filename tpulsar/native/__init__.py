"""Native (C++) host-side kernels, bound via ctypes.

Holds the framework's native runtime tier for host work that NumPy
does inefficiently — currently PSRFITS bit-unpacking (unpack.cpp).
The library is compiled on first use with the system g++ and cached
next to the source; every entry point has a NumPy fallback, so the
package works (slower) without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "unpack.cpp"),
         os.path.join(_HERE, "accel_host.cpp")]


def _host_tag() -> str:
    """Per-host build tag: -march=native produces a CPU-specific .so,
    and this package lives on shared filesystems across heterogeneous
    cluster nodes (the PBS/Slurm deployments) — a binary built on an
    AVX-512 login node must not be dlopen'd into SIGILL on an older
    worker.  Tag by the host's CPU flag set so each micro-architecture
    builds (and caches) its own library."""
    import hashlib
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as fh:
            for ln in fh:
                if ln.startswith(("flags", "Features")):
                    flags = ln
                    break
    except OSError:
        pass
    h = hashlib.sha1(
        (platform.machine() + flags).encode()).hexdigest()[:10]
    return h


_LIB = os.path.join(_HERE, f"_tpulsar_native_{_host_tag()}.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    # -ffp-contract=off: -march=native would otherwise let the
    # compiler contract a*b+c into FMA, changing float rounding vs
    # the NumPy oracles (and the XLA path) these kernels must match
    # bit-for-bit
    cmd = ["g++", "-O3", "-march=native", "-ffp-contract=off",
           "-shared", "-fPIC", "-std=c++17", *_SRCS, "-o", _LIB]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=240)
        if r.returncode != 0:
            # -march=native can be unavailable in odd toolchains;
            # retry portable before giving up (keeping
            # -ffp-contract=off: FMA-baseline targets would otherwise
            # contract a*b+c and break the bit-parity invariant)
            cmd = ["g++", "-O3", "-ffp-contract=off", "-shared",
                   "-fPIC", "-std=c++17", *_SRCS, "-o", _LIB]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=240)
        return r.returncode == 0 and os.path.exists(_LIB)
    except (OSError, subprocess.TimeoutExpired):
        return False


def load() -> ctypes.CDLL | None:
    """The native library, building it on first call (None if no
    toolchain / build failure — callers must fall back)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB) or any(
                os.path.getmtime(_LIB) < os.path.getmtime(s)
                for s in _SRCS):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        i16p = np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS")
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        for name in ("tpulsar_unpack4", "tpulsar_unpack2",
                     "tpulsar_unpack1"):
            fn = getattr(lib, name)
            fn.argtypes = [u8p, i16p, ctypes.c_size_t]
            fn.restype = None
        lib.tpulsar_unpack4_cal.argtypes = [
            u8p, f32p, ctypes.c_size_t, ctypes.c_size_t, f32p, f32p]
        lib.tpulsar_unpack4_cal.restype = None
        lib.tpulsar_unpack4_q8.argtypes = [
            u8p, u8p, ctypes.c_size_t, ctypes.c_size_t, f32p, f32p]
        lib.tpulsar_unpack4_q8.restype = None
        try:
            # the read-in's entry points take the packed rows by
            # ADDRESS (a strided column of the mapped file, read in
            # place); a library built from an older source tree lacks
            # them, and then every caller keeps its NumPy path
            lib.tpulsar_unpack4_q8_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, u8p, ctypes.c_size_t,
                ctypes.c_size_t, ctypes.c_size_t, f32p, f32p,
                ctypes.c_int]
            lib.tpulsar_unpack4_q8_rows.restype = None
            lib.tpulsar_count4.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
                np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")]
            lib.tpulsar_count4.restype = None
        except AttributeError:
            return None
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.tpulsar_accel_stage_topk.argtypes = [
            f32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
            i32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            f32p, i32p, i32p]
        lib.tpulsar_accel_stage_topk.restype = None
        lib.tpulsar_accel_stage_topk_segs.argtypes = [
            f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            i32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            f32p, i32p, i32p]
        lib.tpulsar_accel_stage_topk_segs.restype = None
        # z-chunked pieces entrypoint: guarded — a library built from
        # an older source tree (mtime equal after a clock-skewed
        # copy) simply lacks the symbol and callers fall back to the
        # assembled-pieces layout
        try:
            zfn = lib.tpulsar_accel_stage_topk_zsegs
            zfn.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
                ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, i32p, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, f32p, i32p, i32p]
            zfn.restype = None
        except AttributeError:
            pass
        _lib = lib
        return _lib


def unpack_bits(raw: np.ndarray, nbits: int) -> np.ndarray | None:
    """Unpack (..., nbytes) uint8 -> (..., nsamples) int16 natively;
    None if the native library is unavailable."""
    lib = load()
    if lib is None or nbits not in (4, 2, 1):
        return None
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    per = 8 // nbits
    out = np.empty(raw.shape[:-1] + (raw.shape[-1] * per,),
                   dtype=np.int16)
    fn = {4: lib.tpulsar_unpack4, 2: lib.tpulsar_unpack2,
          1: lib.tpulsar_unpack1}[nbits]
    fn(raw.reshape(-1), out.reshape(-1), raw.size)
    return out


def unpack4_quantize(raw: np.ndarray, a: np.ndarray,
                     b: np.ndarray) -> np.ndarray | None:
    """Fused 4-bit unpack + affine requantization: (nspec, nchan/2)
    uint8 packed -> (nspec, nchan) uint8, out = clip(round(x*a+b)).
    None if the native library is unavailable."""
    lib = load()
    if lib is None:
        return None
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    nspec, nb = raw.shape
    nchan = nb * 2
    a = np.ascontiguousarray(a, dtype=np.float32)
    b = np.ascontiguousarray(b, dtype=np.float32)
    if a.shape != (nchan,) or b.shape != (nchan,):
        return None
    out = np.empty((nspec, nchan), dtype=np.uint8)
    lib.tpulsar_unpack4_q8(raw, out, nspec, nchan, a, b)
    return out


def _row_stride(raw: np.ndarray, nspec: int, nchan: int) -> int:
    """Row stride in bytes of `raw`, the packed 4-bit DATA column of
    some subint rows as the mapped table gives it: (nrows, ...) uint8,
    each row's nspec * nchan / 2 bytes contiguous, the rows apart by
    the table's row length.  Raises where it is anything else: the
    native loops read it by address."""
    if (raw.dtype != np.uint8 or raw.ndim < 2 or not len(raw)
            or raw[0].size != nspec * (nchan // 2) or nchan % 2
            or not raw[0].flags.c_contiguous
            or (len(raw) > 1 and raw.strides[0] < raw[0].size)):
        raise ValueError(
            f"not packed 4-bit rows of {nspec} x {nchan}: dtype "
            f"{raw.dtype}, shape {raw.shape}, strides {raw.strides}")
    return raw.strides[0]


def unpack4_quantize_rows(raw: np.ndarray, out: np.ndarray,
                          a: np.ndarray, b: np.ndarray,
                          flip: bool) -> None:
    """Fused 4-bit unpack + affine requantization of a group of subint
    rows, read in place from the mapped file and written straight into
    `out`, the caller's (nrows * nspec, nchan) uint8 slice of the
    block: out = clip(round(x * a[r] + b[r])) with row r's own
    (a, b) in FILE channel order, the channels reversed in the write
    where `flip`.  The call drops the interpreter's lock: groups that
    write disjoint slices run side by side."""
    nrows, nchan = a.shape
    nspec = out.shape[0] // nrows
    stride = _row_stride(raw, nspec, nchan)
    if (len(raw) != nrows or out.shape != (nrows * nspec, nchan)
            or b.shape != a.shape):
        raise ValueError(f"{len(raw)} rows of packed spectra for a, b "
                         f"{a.shape}, {b.shape} and out {out.shape}")
    load().tpulsar_unpack4_q8_rows(raw.ctypes.data, stride, out, nrows,
                                   nspec, nchan, a, b, int(flip))


def count4(raw_row: np.ndarray, nspec: int, nchan: int) -> np.ndarray:
    """(nchan, 16) uint32: how often each 4-bit sample value occurs in
    each channel (FILE order) of one subint row's packed spectra."""
    _row_stride(raw_row[None], nspec, nchan)
    counts = np.zeros((nchan, 16), np.uint32)
    load().tpulsar_count4(raw_row.ctypes.data, nspec, nchan, counts)
    return counts


def accel_stage_topk(plane: np.ndarray, stages, block_r: int,
                     topk: int):
    """Harmonic-stage sums + per-stage block-max top-k over a
    correlation power plane, bit-identical to the XLA path in
    kernels/accel.py (_harmonic_stage_maxes + fourier.blockmax_topk)
    but cache-tiled for host DRAM bandwidth.

    plane: (nd, nz, nr) float32.  Returns (vals, rbins, zidx) each
    (nd, nstages, topk), or None if the native library is
    unavailable."""
    lib = load()
    if lib is None:
        return None
    if plane.dtype != np.float32 or plane.ndim != 3:
        return None
    stages = np.ascontiguousarray(stages, dtype=np.int32)
    if stages.size == 0 or stages[0] != 1:
        return None     # the kernel seeds its accumulator at stage 1
    plane = np.ascontiguousarray(plane)
    nd, nz, nr = plane.shape
    ns = int(stages.size)
    vals = np.empty((nd, ns, topk), np.float32)
    rbins = np.empty((nd, ns, topk), np.int32)
    zidx = np.empty((nd, ns, topk), np.int32)
    lib.tpulsar_accel_stage_topk(plane, nd, nz, nr, stages, ns,
                                 int(block_r), int(topk),
                                 vals, rbins, zidx)
    return vals, rbins, zidx


def accel_stage_topk_segs(pieces: np.ndarray, width: int, nr: int,
                          stages, block_r: int, topk: int):
    """accel_stage_topk over the RAW overlap-save piece layout
    (nd, nsegs, nz, 2*step) — the plane's transpose/concat/pad never
    happens; the valid-region alignment is applied in index space
    (plane col c -> piece [(c-width)//(2*step), z, (c-width)%(2*step)],
    zero for c < width).  Returns (vals, rbins, zidx) each
    (nd, nstages, topk), or None if unavailable."""
    lib = load()
    if lib is None:
        return None
    if pieces.dtype != np.float32 or pieces.ndim != 4:
        return None
    stages = np.ascontiguousarray(stages, dtype=np.int32)
    if stages.size == 0 or stages[0] != 1:
        return None     # the kernel seeds its accumulator at stage 1
    pieces = np.ascontiguousarray(pieces)
    nd, nsegs, nz, two_step = pieces.shape
    ns = int(stages.size)
    vals = np.empty((nd, ns, topk), np.float32)
    rbins = np.empty((nd, ns, topk), np.int32)
    zidx = np.empty((nd, ns, topk), np.int32)
    lib.tpulsar_accel_stage_topk_segs(
        pieces, nd, nsegs, nz, two_step, int(width), int(nr),
        stages, ns, int(block_r), int(topk), vals, rbins, zidx)
    return vals, rbins, zidx


def has_accel_zsegs() -> bool:
    """True when the library is loadable AND carries the z-chunked
    pieces entrypoint (a stale build without it falls back to the
    assembled-pieces layout instead of failing mid-run)."""
    lib = load()
    return lib is not None and hasattr(lib,
                                       "tpulsar_accel_stage_topk_zsegs")


def accel_stage_topk_zsegs(pieces: list, width: int, nr: int,
                           stages, block_r: int, topk: int):
    """accel_stage_topk over pieces still SPLIT by z-chunk: one
    (nd, nsegs, zc, 2*step) float32 buffer per chunk of the jitted
    correlate program's z loop (kernels/accel._correlate_zpieces),
    addressed through a pointer table — the full-plane concatenate
    never happens on either side.  All chunks share zc except the
    last, which holds the ragged nz remainder.  Returns
    (vals, rbins, zidx) each (nd, nstages, topk), or None if the
    library (or the entrypoint) is unavailable or the layout is
    inconsistent."""
    if not has_accel_zsegs():
        return None
    lib = load()
    stages = np.ascontiguousarray(stages, dtype=np.int32)
    if stages.size == 0 or stages[0] != 1:
        return None     # the kernel seeds its accumulator at stage 1
    if not pieces:
        return None
    arrs = [np.ascontiguousarray(p) for p in pieces]
    first = arrs[0]
    if first.dtype != np.float32 or first.ndim != 4:
        return None
    nd, nsegs, zchunk, two_step = first.shape
    nz = 0
    for i, p in enumerate(arrs):
        if (p.dtype != np.float32 or p.ndim != 4
                or p.shape[0] != nd or p.shape[1] != nsegs
                or p.shape[3] != two_step):
            return None
        # every chunk but the last must be full-height; the last
        # holds the ragged remainder, 1..zchunk rows — taller and
        # ZSegSrc::slab_at's q = zi / zchunk would index past the
        # pointer table
        if i < len(arrs) - 1 and p.shape[2] != zchunk:
            return None
        if not 1 <= p.shape[2] <= zchunk:
            return None
        nz += p.shape[2]
    ns = int(stages.size)
    vals = np.empty((nd, ns, topk), np.float32)
    rbins = np.empty((nd, ns, topk), np.int32)
    zidx = np.empty((nd, ns, topk), np.int32)
    import ctypes as _ct
    table = (_ct.c_void_p * len(arrs))(
        *[p.ctypes.data for p in arrs])
    lib.tpulsar_accel_stage_topk_zsegs(
        table, len(arrs), int(zchunk), nd, nsegs, int(nz),
        int(two_step), int(width), int(nr), stages, ns, int(block_r),
        int(topk), vals, rbins, zidx)
    return vals, rbins, zidx


def unpack4_calibrate(raw: np.ndarray, scales: np.ndarray,
                      offsets: np.ndarray) -> np.ndarray | None:
    """Fused 4-bit unpack + per-channel scale/offset: (nspec, nchan/2)
    uint8 -> (nspec, nchan) float32.  None if unavailable."""
    lib = load()
    if lib is None:
        return None
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    nspec, nb = raw.shape
    nchan = nb * 2
    scales = np.ascontiguousarray(scales, dtype=np.float32)
    offsets = np.ascontiguousarray(offsets, dtype=np.float32)
    if scales.shape != (nchan,) or offsets.shape != (nchan,):
        return None
    out = np.empty((nspec, nchan), dtype=np.float32)
    lib.tpulsar_unpack4_cal(raw, out, nspec, nchan, scales, offsets)
    return out
