"""Native (C++) runtime components and their lazy build.

The reference is pure JVM — its native performance arrives transitively via
Spark/netlib (SURVEY.md "Languages"). This framework's compute path is
XLA/Pallas; the *runtime around it* is native where it is hot:

- ``src/eventlog.cc``  — append-only event-store engine (the HBase-driver
  role, data/.../storage/hbase/ in the reference)
- ``src/csr_builder.cc`` — COO → degree-bucketed padded rows (the host data
  loader feeding device ingest)

The shared library is compiled on first use with the system ``g++`` (no pip
deps, mirroring how the reference compiles engines on demand via ``pio
build`` → sbt, tools/.../commands/Engine.scala:158-225) and cached next to
the sources, keyed by a digest of their CONTENT (never mtimes). Callers
check :func:`load` for ``None`` and fall back to pure-Python paths — fine
for tests and small stores, a hang at 20M events: ``chip_smoke.py`` fails
its *native* phase when the library does not build and load.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

_SRC_DIR = Path(__file__).parent / "src"
_BUILD_DIR = Path(__file__).parent / "_build"
_SOURCES = ("eventlog.cc", "csr_builder.cc", "jsonparse.cc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _compile_cmd() -> list:
    return [os.environ.get("CXX", "g++"),
            "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _source_digest() -> str:
    """sha256 over the compile command and every source's bytes — the
    build key. Content, not mtime: a copied or checked-out tree (whose
    mtimes say nothing) can never load a library built from other
    sources."""
    h = hashlib.sha256(" ".join(_compile_cmd()).encode())
    for name in _SOURCES:
        h.update(name.encode())
        h.update((_SRC_DIR / name).read_bytes())
    return h.hexdigest()[:16]


def lib_path() -> Path:
    return _BUILD_DIR / f"libpio_native-{_source_digest()}.so"


def build(force: bool = False) -> Path:
    """Compile the native library (idempotent; keyed by the digest of
    the sources in its file name)."""
    so = lib_path()
    if not force and so.exists():
        return so
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # compile to a process-unique temp name, then atomically rename: two
    # processes racing a cold build must never CDLL a half-written .so
    tmp = so.with_suffix(f".so.tmp{os.getpid()}")
    cmd = [*_compile_cmd(), *[str(_SRC_DIR / s) for s in _SOURCES],
           "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    finally:
        tmp.unlink(missing_ok=True)
    # libraries of other source digests are dead weight (a process that
    # still has one mapped keeps its inode)
    for old in _BUILD_DIR.glob("libpio_native*.so"):
        if old != so:
            old.unlink(missing_ok=True)
    return so


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    u64p = c.POINTER(c.c_uint64)
    i64p = c.POINTER(c.c_int64)
    # eventlog
    lib.pio_evlog_open.restype = c.c_void_p
    lib.pio_evlog_open.argtypes = [c.c_char_p]
    lib.pio_evlog_close.restype = None
    lib.pio_evlog_close.argtypes = [c.c_void_p]
    lib.pio_evlog_append.restype = c.c_int64
    lib.pio_evlog_append.argtypes = [
        c.c_void_p, c.c_int64, c.c_uint64, c.c_uint64, c.c_uint64,
        c.c_uint64, c.c_char_p, c.c_uint32,
    ]
    lib.pio_evlog_tombstone.restype = c.c_int64
    lib.pio_evlog_tombstone.argtypes = [c.c_void_p, c.c_int64]
    lib.pio_evlog_count.restype = c.c_int64
    lib.pio_evlog_count.argtypes = [c.c_void_p]
    lib.pio_evlog_compact_copy.restype = c.c_int64
    lib.pio_evlog_compact_copy.argtypes = [c.c_void_p, c.c_char_p]
    lib.pio_evlog_query.restype = c.c_int64
    lib.pio_evlog_query.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_uint64, c.c_uint64,
        u64p, c.c_int32, c.c_int32, c.c_int64, i64p, c.c_int64,
    ]
    lib.pio_evlog_find_id.restype = c.c_int64
    lib.pio_evlog_find_id.argtypes = [c.c_void_p, c.c_uint64, i64p, c.c_int64]
    lib.pio_evlog_read.restype = c.c_int32
    lib.pio_evlog_read.argtypes = [
        c.c_void_p, c.c_int64, c.c_char_p, c.c_int32,
    ]
    lib.pio_evlog_sync.restype = c.c_int64
    lib.pio_evlog_sync.argtypes = [c.c_void_p]
    lib.pio_evlog_entry_count.restype = c.c_int64
    lib.pio_evlog_entry_count.argtypes = [c.c_void_p]
    lib.pio_evlog_dead_count.restype = c.c_int64
    lib.pio_evlog_dead_count.argtypes = [c.c_void_p]
    lib.pio_evlog_file_size.restype = c.c_int64
    lib.pio_evlog_file_size.argtypes = [c.c_void_p]
    lib.pio_evlog_read_frames.restype = c.c_int64
    lib.pio_evlog_read_frames.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_char_p, i64p]
    lib.pio_evlog_append_frames.restype = c.c_int64
    lib.pio_evlog_append_frames.argtypes = [c.c_void_p, c.c_char_p,
                                            c.c_int64]
    lib.pio_evlog_hash_ids.restype = c.c_int64
    lib.pio_evlog_hash_ids.argtypes = [c.c_char_p, i64p, c.c_int64,
                                       c.POINTER(c.c_uint64)]
    # columnar interaction scan ([min, max) entry range + thread count; the
    # mutex is held only for the header snapshot — see eventlog.cc)
    lib.pio_evlog_scan_interactions.restype = c.c_void_p
    lib.pio_evlog_scan_interactions.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_int64, c.c_char_p,
        c.c_char_p, c.POINTER(c.c_char_p), c.POINTER(c.c_double), c.c_int32,
        c.c_char_p, c.c_double, c.c_int32,
    ]
    lib.pio_scan_nnz.restype = c.c_int64
    lib.pio_scan_nnz.argtypes = [c.c_void_p]
    lib.pio_scan_lock_held_ns.restype = c.c_int64
    lib.pio_scan_lock_held_ns.argtypes = [c.c_void_p]
    lib.pio_scan_n_ids.restype = c.c_int64
    lib.pio_scan_n_ids.argtypes = [c.c_void_p, c.c_int32]
    lib.pio_scan_ids_bytes.restype = c.c_int64
    lib.pio_scan_ids_bytes.argtypes = [c.c_void_p, c.c_int32]
    lib.pio_scan_fill.restype = None
    lib.pio_scan_fill.argtypes = [
        c.c_void_p, c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        c.POINTER(c.c_float),
    ]
    lib.pio_scan_fill_times.restype = None
    lib.pio_scan_fill_times.argtypes = [c.c_void_p, i64p]
    lib.pio_scan_copy_ids.restype = None
    lib.pio_scan_copy_ids.argtypes = [
        c.c_void_p, c.c_int32, c.c_char_p, i64p,
    ]
    lib.pio_scan_free.restype = None
    lib.pio_scan_free.argtypes = [c.c_void_p]
    lib.pio_evlog_append_bulk.restype = c.c_int64
    lib.pio_evlog_append_bulk.argtypes = [
        c.c_void_p, c.c_int64, i64p, c.c_char_p, i64p, c.c_char_p,
    ]
    lib.pio_evlog_append_interactions.restype = c.c_int64
    lib.pio_evlog_append_interactions.argtypes = [
        c.c_void_p, c.c_int64, i64p,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_float),
        c.c_char_p, i64p, c.c_int64,
        c.c_char_p, i64p, c.c_int64,
        c.c_char_p, c.c_char_p, c.c_char_p, c.c_char_p, c.c_uint64,
    ]
    # csr builder
    pp_i32 = c.POINTER(c.POINTER(c.c_int32))
    pp_f32 = c.POINTER(c.POINTER(c.c_float))
    lib.pio_csr_plan.restype = c.c_int64
    lib.pio_csr_plan.argtypes = [
        c.POINTER(c.c_int32), c.c_int64, c.c_int64, c.c_int32, c.c_int32,
        c.c_int32, i64p,
    ]
    lib.pio_csr_fill.restype = c.c_int64
    lib.pio_csr_fill.argtypes = [
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_float),
        c.c_int64, c.c_int64, c.c_int32, c.c_int32, c.c_int32, i64p,
        pp_i32, pp_i32, pp_f32, pp_f32,
    ]
    # uniform-batch JSON parser (REST ingest hot path)
    lib.pio_parse_uniform_batch.restype = c.c_int64
    lib.pio_parse_uniform_batch.argtypes = [
        c.c_char_p, c.c_int64, c.c_int64,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_float),
        c.c_char_p, c.c_int64, i64p, i64p,
        c.c_char_p, c.c_int64, i64p, i64p,
        c.c_char_p, c.c_int64, i64p,
    ]


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            so = build()
            lib = ctypes.CDLL(str(so))
            _declare(lib)
            _lib = lib
        except Exception as exc:  # toolchain missing / compile error
            _load_failed = True
            logger.warning(
                "native library unavailable (%s); using pure-Python "
                "fallbacks", exc)
    return _lib


def fnv1a64(data: bytes) -> int:
    """FNV-1a 64-bit — the hash the eventlog headers use for predicate
    pushdown. 0 is reserved as the "no filter" sentinel, so real hashes of 0
    are mapped to 1 (a one-in-2⁶⁴ bias, invisible next to the exact-match
    recheck in the DAO)."""
    h = 0xCBF29CE484222325
    for byte in data:
        h ^= byte
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h or 1


def fnv1a64_table(blob: bytes, offsets):
    """FNV-1a of every entry of an interned id table (blob + int64
    offsets, the IdTable layout) in ONE native crossing — the
    writer-shard spray hashes whole tables per batch, and a per-id
    Python loop is ~1000x the cost of the hash itself. Returns a
    uint64 array of len(offsets)-1; falls back to pure Python when the
    native library is unavailable."""
    import numpy as np

    n = max(len(offsets) - 1, 0)
    offs = np.ascontiguousarray(offsets, np.int64)
    out = np.empty(n, np.uint64)
    lib = load()
    if lib is not None:
        rc = lib.pio_evlog_hash_ids(
            blob, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
        if rc == n:
            return out
    for i in range(n):
        out[i] = fnv1a64(blob[offs[i]:offs[i + 1]])
    return out
