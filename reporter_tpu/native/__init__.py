"""ctypes binding for the native core (native/reporter_native.cc).

``get_lib()`` lazily compiles the shared library with g++ on first use and
returns the loaded CDLL with argtypes configured, or None when no compiler
is available -- every caller has a pure-Python fallback (the framework's
native tier accelerates, never gates)."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SRC = os.path.join(_NATIVE_DIR, "reporter_native.cc")
_LIB = os.path.join(_NATIVE_DIR, "libreporter_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")


def _compile(out_path: str) -> bool:
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-Wall",
             "-o", out_path, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        return True
    except Exception as e:
        log.warning("native build failed, using Python fallbacks: %s", e)
        return False


def _build() -> bool:
    if not os.path.exists(_SRC):
        return False
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return True
    return _compile(_LIB)


# every exported symbol with (restype, argtypes); configuration is tolerant
# of symbols a stale .so predates -- callers hasattr-check before use, so a
# partially-configured library still accelerates everything it exports
_SYMBOLS = {
    "rn_tile_write": (ctypes.c_int, [
        ctypes.c_char_p, ctypes.c_uint32, _f64p, _f64p, ctypes.c_uint32,
        _u32p, _u32p, _f32p, _u8p, _u8p, _i64p, _i64p, _u32p,
        ctypes.c_uint32, _f64p, _f64p,
    ]),
    "rn_tile_header": (ctypes.c_int, [ctypes.c_char_p, _u32p]),
    "rn_tile_read": (ctypes.c_int, [
        ctypes.c_char_p, _f64p, _f64p, _u32p, _u32p, _f32p, _u8p, _u8p,
        _i64p, _i64p, _u32p, _f64p, _f64p,
    ]),
    "rn_parse_shard": (ctypes.c_int64, [
        ctypes.c_char_p, ctypes.c_int64, _f64p, _f64p, _i64p, _i32p,
        _i64p, _i32p, ctypes.c_int64,
    ]),
    "rn_abi_version": (ctypes.c_uint32, []),
    "rn_ubodt_build": (ctypes.c_void_p, [
        ctypes.c_int64, _i32p, _i32p, _i32p, _f32p, _f32p,
        ctypes.c_double, ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
    ]),
    "rn_ubodt_fetch": (None, [
        ctypes.c_void_p, _i32p, _i32p, _f32p, _f32p, _i32p,
    ]),
    "rn_cuckoo_pack": (ctypes.c_int64, [
        ctypes.c_int64, _i32p, _i32p, _f32p, _f32p, _i32p,
        ctypes.c_int64, _i32p,
    ]),
    "rn_wide_pack": (ctypes.c_int64, [
        ctypes.c_int64, _i32p, _i32p, _f32p, _f32p, _i32p,
        ctypes.c_int64, _i32p,
    ]),
    "rn_associate_batch": (ctypes.c_int32, [
        # graph
        _i32p, _i32p, _f32p, _i32p, _f32p, _u8p, _i64p, _i64p, _f32p,
        # ubodt (packed table + bmask + entries-per-bucket + rows)
        _i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        # matches
        ctypes.c_int64, ctypes.c_int64, _i32p, _f32p, _u8p, _f64p, _i32p,
        # params
        ctypes.c_double, ctypes.c_double,
        # outputs
        ctypes.c_int64, ctypes.c_int64, _i64p, _u8p, _i64p, _f64p, _f64p,
        _f64p, _u8p, _f64p, _i32p, _i32p, _i64p, _i64p,
    ]),
    "rn_associate_batch_mt": (ctypes.c_int32, [
        # graph
        _i32p, _i32p, _f32p, _i32p, _f32p, _u8p, _i64p, _i64p, _f32p,
        # ubodt (packed table + bmask + entries-per-bucket + rows)
        _i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        # matches
        ctypes.c_int64, ctypes.c_int64, _i32p, _f32p, _u8p, _f64p, _i32p,
        # params
        ctypes.c_double, ctypes.c_double, ctypes.c_int32,
        # outputs
        ctypes.c_int64, ctypes.c_int64, _i64p, _u8p, _i64p, _f64p, _f64p,
        _f64p, _u8p, _f64p, _i32p, _i32p, _i64p, _i64p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]),
}


def _configure(lib: ctypes.CDLL):
    """Configure all exported symbols.  Returns (lib, missing_names)."""
    missing = []
    for name, (restype, argtypes) in _SYMBOLS.items():
        try:
            fn = getattr(lib, name)
        except AttributeError:
            missing.append(name)
            continue
        fn.restype = restype
        fn.argtypes = argtypes
    return lib, missing


def get_lib(force_rebuild: bool = False) -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None and not force_rebuild:
            return _lib
        if _tried and not force_rebuild:
            return _lib
        _tried = True
        if force_rebuild and os.path.exists(_LIB):
            os.remove(_LIB)
        if not _build():
            return None
        try:
            _lib, missing = _configure(ctypes.CDLL(_LIB))
        except OSError as e:
            log.warning("native library load failed: %s", e)
            _lib = None
            return _lib
        if missing:
            # a stale .so that predates newly-added symbols but passes the
            # mtime check (archive/copy with preserved timestamps): rebuild
            # to a temp path and dlopen THAT (dlopen caches by path, so
            # re-loading _LIB would return the stale mapping).  If the
            # rebuild fails -- e.g. no compiler on this host -- the stale
            # library stays loaded and keeps accelerating every symbol it
            # does export ("the native tier accelerates, never gates").
            log.warning("native library missing symbols %s; rebuilding", missing)
            try:
                import shutil
                import tempfile

                tmpdir = tempfile.mkdtemp(prefix="reporter_native_")
                tmp = os.path.join(tmpdir, "libreporter_native_rebuilt.so")
                if _compile(tmp):
                    fresh, still_missing = _configure(ctypes.CDLL(tmp))
                    if not still_missing:
                        _lib = fresh
                        try:
                            # atomic same-directory replace: concurrent
                            # dlopens never see a torn file, and the old
                            # inode stays intact under existing mappings
                            side = _LIB + ".new"
                            shutil.copy2(tmp, side)
                            os.replace(side, _LIB)
                        except OSError:
                            log.warning("could not refresh %s on disk", _LIB)
                shutil.rmtree(tmpdir, ignore_errors=True)
            except Exception as e2:
                log.warning(
                    "native rebuild failed (%s); keeping stale library's "
                    "exported symbols", e2,
                )
        return _lib


def parse_shard_columns(data: bytes, lib=None):
    """Parse shard rows 'uuid,epoch,lat,lon,acc' into columns: (codes,
    uuids, time, lat, lon, acc), where row i's uuid is ``uuids[codes[i]]``
    and ``uuids`` holds each distinct uuid once, in order of first
    appearance.  Torn rows are skipped.  Native when available,
    numpy/python otherwise."""
    if lib is None:
        lib = get_lib()
    n_lines = data.count(b"\n") + 1
    if lib is not None:
        lat = np.empty(n_lines, np.float64)
        lon = np.empty(n_lines, np.float64)
        tm = np.empty(n_lines, np.int64)
        acc = np.empty(n_lines, np.int32)
        uoff = np.empty(n_lines, np.int64)
        ulen = np.empty(n_lines, np.int32)
        n = lib.rn_parse_shard(data, len(data), lat, lon, tm, acc, uoff, ulen, n_lines)
        codes, uuids = _group_uuids(data, uoff[:n], ulen[:n])
        return codes, uuids, tm[:n].copy(), lat[:n].copy(), lon[:n].copy(), acc[:n].copy()
    ids: dict = {}
    codes, tms, lats, lons, accs = [], [], [], [], []
    for line in data.decode(errors="replace").splitlines():
        # parse the whole row before appending anything, so a row that fails
        # on a late field can't leave the columns misaligned
        try:
            uuid, tm_, lat_, lon_, acc_ = line.strip().split(",")
            if not uuid:
                continue
            row = (int(tm_), float(lat_), float(lon_), int(acc_))
        except ValueError:
            continue
        codes.append(ids.setdefault(uuid, len(ids)))
        tms.append(row[0])
        lats.append(row[1])
        lons.append(row[2])
        accs.append(row[3])
    return (
        np.asarray(codes, np.int64),
        list(ids),
        np.asarray(tms, np.int64),
        np.asarray(lats, np.float64),
        np.asarray(lons, np.float64),
        np.asarray(accs, np.int32),
    )


def _group_uuids(data: bytes, off: np.ndarray, ln: np.ndarray):
    """(codes, uuids) for the uuid byte ranges ``data[off:off+ln]``: one
    fixed-width key per row (its length, then its bytes zero-padded) and
    one sort group the rows; each distinct uuid is decoded once."""
    n = len(off)
    if n == 0:
        return np.zeros(0, np.int64), []
    width = int(ln.max())
    raw = np.frombuffer(data, np.uint8)
    last = len(raw) - 1
    key = np.zeros((n, 4 + width), np.uint8)
    key[:, :4] = ln.astype("<i4").view(np.uint8).reshape(n, 4)
    for j in range(width):
        key[:, 4 + j] = np.where(j < ln, raw[np.minimum(off + j, last)], 0)
    _, first, inv = np.unique(key.view("V%d" % (4 + width)).ravel(),
                              return_index=True, return_inverse=True)
    order = np.argsort(first)
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order))
    # "replace": a torn multi-byte character must not abort the batch; two
    # byte strings that decode alike are one uuid, as a dict by name has it
    ids: dict = {}
    remap = np.array([
        ids.setdefault(data[o : o + m].decode(errors="replace"), len(ids))
        for o, m in zip(off[first[order]].tolist(), ln[first[order]].tolist())
    ], np.int64)
    return remap[rank[inv.ravel()]], list(ids)


# -- CPython extension: wire-format record materialisation -------------------
#
# Separate from the ctypes CDLL above because it constructs Python objects
# (lists/dicts) directly -- that needs the CPython C API, not a plain shared
# library.  Same lazy-compile contract: accelerates, never gates.

_EXT_SRC = os.path.join(_NATIVE_DIR, "records_ext.c")
_ext_lock = threading.Lock()
_ext_mod = None
_ext_tried = False


def get_records_ext(force_rebuild: bool = False):
    """Compile (lazily) and import native/records_ext.c; None on failure."""
    global _ext_mod, _ext_tried
    with _ext_lock:
        if (_ext_mod is not None or _ext_tried) and not force_rebuild:
            return _ext_mod
        _ext_tried = True
        if not os.path.exists(_EXT_SRC):
            return None
        import sysconfig

        suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
        ext_path = os.path.join(_NATIVE_DIR, "_records%s" % suffix)
        try:
            stale = not (
                os.path.exists(ext_path)
                and os.path.getmtime(ext_path) >= os.path.getmtime(_EXT_SRC)
            )
            if stale or force_rebuild:
                # compile to a temp path and atomically replace: dlopen
                # caches by inode, and gcc truncating a still-mapped .so in
                # place could crash a process executing it (same hazard
                # get_lib's rebuild path documents)
                inc = sysconfig.get_paths()["include"]
                tmp = ext_path + ".build"
                subprocess.run(
                    ["gcc", "-O2", "-fPIC", "-shared", "-I", inc,
                     "-o", tmp, _EXT_SRC],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp, ext_path)
            import importlib.util

            # spec name "_records" so the loader finds PyInit__records; the
            # module is returned without touching sys.modules
            spec = importlib.util.spec_from_file_location("_records", ext_path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _ext_mod = mod
        except Exception as e:  # noqa: BLE001 - never gate on the fast path
            log.warning("records extension unavailable, using Python loop: %s", e)
            _ext_mod = None
        return _ext_mod
