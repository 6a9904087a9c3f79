"""ctypes bindings for the native library (csrc/).

``load_native`` runs ``make`` in csrc/ on first use — make decides what
is stale (sources, headers, the Makefile and a build stamp that covers
the sanitizer flavor AND this host's CPU, because the library is built
``-march=native``) — then loads ``libpaddle_tpu_native.so``. A build or
load that FAILS raises with the compiler's output. Only a host with no
``make`` at all gets ``None``: there ``FeasignIndex`` and the tables run
their pure-Python implementations (slower, flagged via
``native_available()``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from ..core.profiler import RecordEvent

__all__ = ["FeasignIndex", "NativeSparseTableEngine", "SsdTableEngine",
           "native_available", "load_native", "build_native", "dedup_u64"]

_CSRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "csrc"))
_LIB_PATH = os.path.join(_CSRC, "libpaddle_tpu_native.so")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_NO_TOOLCHAIN = False


def build_native(force: bool = False) -> bool:
    """``make`` the library from csrc/ (``force`` rebuilds everything —
    chip_smoke.py uses it so the library it loads was compiled in that
    run, on that machine). Returns False when the host has no ``make``;
    raises RuntimeError with the build output when the build fails. The
    span ``pt.native.build`` says whether make wrote a library
    (``built`` 1) or found it current (0)."""
    with RecordEvent("pt.native.build", built=0) as ev:
        before = _lib_mtime()
        try:
            out = subprocess.run(
                ["make", "-s"] + (["-B"] if force else []), cwd=_CSRC,
                capture_output=True, text=True, timeout=600)
        except FileNotFoundError:
            return False
        ev["built"] = int(_lib_mtime() != before)
    if out.returncode != 0:
        raise RuntimeError(
            f"native build failed (make rc={out.returncode} in {_CSRC}):\n"
            f"{out.stdout[-2000:]}{out.stderr[-4000:]}")
    return True


def _lib_mtime() -> Optional[int]:
    try:
        return os.stat(_LIB_PATH).st_mtime_ns
    except FileNotFoundError:
        return None


def load_native() -> Optional[ctypes.CDLL]:
    global _LIB, _NO_TOOLCHAIN
    with _LOCK:
        if _LIB is not None or _NO_TOOLCHAIN:
            return _LIB
        if not build_native():
            _NO_TOOLCHAIN = True
            return None
        lib = ctypes.CDLL(_LIB_PATH)
        _configure(lib)
        _LIB = lib
        return _LIB


def _configure(lib: ctypes.CDLL) -> None:
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.psidx_create.restype = ctypes.c_void_p
    lib.psidx_create.argtypes = [ctypes.c_uint64]
    lib.psidx_destroy.argtypes = [ctypes.c_void_p]
    lib.psidx_size.restype = ctypes.c_int64
    lib.psidx_size.argtypes = [ctypes.c_void_p]
    lib.psidx_row_capacity.restype = ctypes.c_int64
    lib.psidx_row_capacity.argtypes = [ctypes.c_void_p]
    lib.psidx_lookup.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i32p]
    if hasattr(lib, "psidx_lookup_mt"):
        lib.psidx_lookup_mt.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64,
                                        i32p, ctypes.c_int32]
    lib.psidx_lookup_or_insert.restype = ctypes.c_int64
    lib.psidx_lookup_or_insert.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, i32p]
    lib.psidx_erase.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64]
    lib.psidx_items.argtypes = [ctypes.c_void_p, u64p, i32p]
    if hasattr(lib, "ps_dedup_u64"):
        lib.ps_dedup_u64.restype = ctypes.c_int64
        lib.ps_dedup_u64.argtypes = [u64p, ctypes.c_int64, u64p,
                                     ctypes.c_int32]


def native_available() -> bool:
    return load_native() is not None


def _cuckoo_lib():
    """The native library with csrc/cuckoo.cc's two entry points typed."""
    lib = load_native()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if not getattr(lib, "_cuckoo_configured", False):
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.cuckoo_build.restype = ctypes.c_int64
        lib.cuckoo_build.argtypes = [u64p, i32p, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_uint32,
                                     u32p, i32p]
        lib.cuckoo_placement.restype = ctypes.c_int64
        lib.cuckoo_placement.argtypes = [u32p, i32p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64,
                                         u64p, i32p]
        lib._cuckoo_configured = True
    return lib


def _u32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def cuckoo_build(keys: np.ndarray, rows: np.ndarray, nbuckets: int,
                 seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Build a static bucketized-cuckoo table (csrc/cuckoo.cc) mapping
    uint64 feasign → int32 row; returns (key u32[nbuckets, 8], row
    i32[nbuckets, 4]) for upload to HBM (ps/device_hash.py probes them
    in-graph). A bucket's row of ``key`` is its four hi halves then its
    four lo halves — the builder writes that layout itself, no host pass
    after it. Raises RuntimeError if the native lib is unavailable or
    the build fails (caller retries with a new seed)."""
    lib = _cuckoo_lib()
    keys = np.ascontiguousarray(keys, np.uint64)
    rows = np.ascontiguousarray(rows, np.int32)
    key = np.empty((nbuckets, 8), np.uint32)
    row = np.empty((nbuckets, 4), np.int32)
    fails = int(lib.cuckoo_build(
        _u64(keys), _i32(rows), len(keys), nbuckets, ctypes.c_uint32(seed),
        _u32(key), _i32(row)))
    if fails:
        raise RuntimeError(f"cuckoo build failed to place {fails} keys")
    return key, row


def cuckoo_placement(key: np.ndarray, row: np.ndarray, n: int, shards: int,
                     shard_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """The placement of a :func:`cuckoo_build` whose rows were 0..n-1,
    in cache-row order (csrc/cuckoo.cc): (keys u64[n], rows i32[n]
    ascending), slot ``s`` of bucket ``b`` being row ``(b mod shards) *
    shard_rows + (b div shards) * 4 + s``."""
    keys = np.empty(n, np.uint64)
    rows = np.empty(n, np.int32)
    got = int(_cuckoo_lib().cuckoo_placement(
        _u32(key), _i32(row), len(key), shards, shard_rows, _u64(keys),
        _i32(rows)))
    if got != n:
        raise RuntimeError(f"cuckoo placement holds {got} keys, not {n}")
    return keys, rows


def table_native_params(shard_num: int, accessor: str, acc_cfg,
                        seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(iparams i32[6], fparams f32[17]) for the native table ABI — the
    ONE definition of the layout `pstpu::parse_table_config`
    (csrc/sparse_table.h) reads, shared by the in-process engines and
    the RPC create payload. ``acc_cfg`` is an AccessorConfig."""
    sgd = acc_cfg.sgd
    # the ABI's seed is a non-negative i32: a larger one (a benchmark seed
    # may pass 2**31) folds into 31 bits instead of overflowing
    ip = np.asarray(
        [shard_num, _ACCESSOR_IDS[accessor], acc_cfg.embedx_dim,
         _RULE_IDS[acc_cfg.embed_sgd_rule], _RULE_IDS[acc_cfg.embedx_sgd_rule],
         int(seed) & 0x7FFFFFFF], np.int32)
    fp = np.asarray(
        [acc_cfg.nonclk_coeff, acc_cfg.click_coeff, acc_cfg.base_threshold,
         acc_cfg.delta_threshold, acc_cfg.delta_keep_days,
         acc_cfg.show_click_decay_rate, acc_cfg.delete_threshold,
         acc_cfg.delete_after_unseen_days, acc_cfg.embedx_threshold,
         sgd.learning_rate, sgd.initial_g2sum, sgd.initial_range,
         sgd.weight_bounds[0], sgd.weight_bounds[1],
         sgd.beta1, sgd.beta2, sgd.ada_epsilon], np.float32)
    return ip, fp


def dedup_u64(keys: np.ndarray, n_threads: Optional[int] = None) -> np.ndarray:
    """Parallel distinct-keys extraction (the PreBuildTask 16-thread shard
    dedup, ps_gpu_wrapper.cc:92): hash-partitioned bucket dedup across
    threads. Returns the unique keys in a deterministic (but unsorted)
    order; falls back to np.unique without the native lib."""
    keys = np.ascontiguousarray(keys, np.uint64).reshape(-1)
    lib = load_native()
    if lib is None or not hasattr(lib, "ps_dedup_u64"):
        return np.unique(keys)
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    out = np.empty(len(keys), np.uint64)
    n = int(lib.ps_dedup_u64(_u64(keys), len(keys), _u64(out),
                             ctypes.c_int32(n_threads)))
    return out[:n].copy()


def _u64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class FeasignIndex:
    """Batched feasign→row map (native-backed; python-dict fallback)."""

    def __init__(self, capacity_hint: int = 1024) -> None:
        self._lib = load_native()
        if self._lib is not None:
            self._h = self._lib.psidx_create(ctypes.c_uint64(capacity_hint))
        else:
            self._d: dict = {}
            self._free: list = []
            self._row_keys: list = []

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.psidx_destroy(self._h)
            self._h = None

    def __len__(self) -> int:
        if self._lib is not None:
            return int(self._lib.psidx_size(self._h))
        return len(self._d)

    @property
    def row_capacity(self) -> int:
        """Highest row id ever allocated + 1 (size for value arrays)."""
        if self._lib is not None:
            return int(self._lib.psidx_row_capacity(self._h))
        return len(self._row_keys)

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = np.empty(len(keys), np.int32)
        if self._lib is not None:
            if hasattr(self._lib, "psidx_lookup_mt"):
                nt = min(8, os.cpu_count() or 1)
                self._lib.psidx_lookup_mt(self._h, _u64(keys), len(keys),
                                          _i32(rows), nt)
            else:
                self._lib.psidx_lookup(self._h, _u64(keys), len(keys), _i32(rows))
        else:
            for i, k in enumerate(keys):
                rows[i] = self._d.get(int(k), -1)
        return rows

    def lookup_or_insert(self, keys: np.ndarray) -> Tuple[np.ndarray, int]:
        """Returns (rows, num_new). Insert-on-miss pull semantics."""
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = np.empty(len(keys), np.int32)
        if self._lib is not None:
            n_new = int(
                self._lib.psidx_lookup_or_insert(self._h, _u64(keys), len(keys), _i32(rows))
            )
            return rows, n_new
        n_new = 0
        for i, k in enumerate(keys):
            k = int(k)
            row = self._d.get(k)
            if row is None:
                if self._free:
                    row = self._free.pop()
                    self._row_keys[row] = k
                else:
                    row = len(self._row_keys)
                    self._row_keys.append(k)
                self._d[k] = row
                n_new += 1
            rows[i] = row
        return rows, n_new

    def erase(self, keys: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint64)
        if self._lib is not None:
            self._lib.psidx_erase(self._h, _u64(keys), len(keys))
        else:
            for k in keys:
                row = self._d.pop(int(k), None)
                if row is not None:
                    self._free.append(row)

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        """(keys, rows) of all live entries (save/shrink iteration)."""
        n = len(self)
        keys = np.empty(n, np.uint64)
        rows = np.empty(n, np.int32)
        if self._lib is not None:
            self._lib.psidx_items(self._h, _u64(keys), _i32(rows))
        else:
            for j, (k, r) in enumerate(self._d.items()):
                keys[j] = k
                rows[j] = r
        return keys, rows


def _configure_slotp(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.slotp_create.restype = ctypes.c_void_p
    lib.slotp_create.argtypes = [ctypes.c_int, u8p, u8p]
    lib.slotp_destroy.argtypes = [ctypes.c_void_p]
    lib.slotp_parse.restype = ctypes.c_int64
    lib.slotp_parse.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.slotp_lines.restype = ctypes.c_int64
    lib.slotp_lines.argtypes = [ctypes.c_void_p]
    lib.slotp_errors.restype = ctypes.c_int64
    lib.slotp_errors.argtypes = [ctypes.c_void_p]
    lib.slotp_slot_value_count.restype = ctypes.c_int64
    lib.slotp_slot_value_count.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.slotp_slot_fetch.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, i32p]
    lib.slotp_reset.argtypes = [ctypes.c_void_p]


class SlotParser:
    """Batched MultiSlot text parser (native; Python fallback).

    slots: list of (name, is_float, used). ``parse`` consumes a text
    block; ``fetch`` returns {slot_name: (values, lengths)} CSR pairs for
    the used slots and resets for the next block.
    """

    def __init__(self, slots) -> None:
        self.slots = [(str(n), bool(f), bool(u)) for n, f, u in slots]
        self._lib = load_native()
        if self._lib is not None:
            if not hasattr(self._lib, "_slotp_configured"):
                _configure_slotp(self._lib)
                self._lib._slotp_configured = True
            is_float = np.asarray([f for _, f, _ in self.slots], np.uint8)
            used = np.asarray([u for _, _, u in self.slots], np.uint8)
            self._h = self._lib.slotp_create(
                len(self.slots),
                is_float.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                used.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
        else:
            self._py_rows = []
            self._py_errors = 0

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.slotp_destroy(self._h)
            self._h = None

    def parse(self, text) -> int:
        data = text.encode() if isinstance(text, str) else bytes(text)
        if self._lib is not None:
            return int(self._lib.slotp_parse(self._h, data, len(data)))
        return self._py_parse(data.decode())

    @property
    def errors(self) -> int:
        if self._lib is not None:
            return int(self._lib.slotp_errors(self._h))
        return self._py_errors

    @property
    def lines(self) -> int:
        if self._lib is not None:
            return int(self._lib.slotp_lines(self._h))
        return len(self._py_rows)

    def fetch(self):
        out = {}
        if self._lib is not None:
            n_lines = self.lines
            for s, (name, is_float, used) in enumerate(self.slots):
                if not used:
                    continue
                count = int(self._lib.slotp_slot_value_count(self._h, s))
                values = np.empty(count, np.float32 if is_float else np.uint64)
                lengths = np.empty(n_lines, np.int32)
                self._lib.slotp_slot_fetch(
                    self._h, s, values.ctypes.data_as(ctypes.c_void_p),
                    lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                )
                out[name] = (values, lengths)
            self._lib.slotp_reset(self._h)
            return out
        # python fallback
        for s, (name, is_float, used) in enumerate(self.slots):
            if not used:
                continue
            vals, lens = [], []
            for row in self._py_rows:
                v = row[s]
                vals.extend(v)
                lens.append(len(v))
            out[name] = (
                np.asarray(vals, np.float32 if is_float else np.uint64),
                np.asarray(lens, np.int32),
            )
        self._py_rows = []
        self._py_errors = 0
        return out

    def _py_parse(self, text: str) -> int:
        ok = 0
        for line in text.splitlines():
            if not line.strip():
                continue
            toks = line.split()
            pos = 0
            row = []
            good = True
            for name, is_float, used in self.slots:
                try:
                    n = int(toks[pos]); pos += 1
                    if n < 0:
                        raise ValueError
                    vals = toks[pos : pos + n]
                    if len(vals) != n:
                        raise ValueError
                    pos += n
                    if used:
                        row.append([float(v) if is_float else int(v) for v in vals])
                    else:
                        for v in vals:
                            float(v)
                except (ValueError, IndexError):
                    good = False
                    break
            if good:
                self._py_rows.append(row)
                ok += 1
            else:
                self._py_errors += 1
        return ok


# ---------------------------------------------------------------------------
# Native sparse-table engine (csrc/sparse_table.cc)
# ---------------------------------------------------------------------------

_RULE_IDS = {"naive": 0, "adagrad": 1, "std_adagrad": 2, "adam": 3}
_ACCESSOR_IDS = {"ctr": 0, "CtrCommonAccessor": 0, "sparse": 1, "SparseAccessor": 1}


def _configure_pst(lib: ctypes.CDLL) -> None:
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.pst_create.restype = ctypes.c_void_p
    lib.pst_create.argtypes = [i32p, f32p]
    lib.pst_destroy.argtypes = [ctypes.c_void_p]
    for fn in ("pst_pull_dim", "pst_push_dim", "pst_full_dim"):
        getattr(lib, fn).restype = ctypes.c_int32
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.pst_size.restype = ctypes.c_int64
    lib.pst_size.argtypes = [ctypes.c_void_p]
    lib.pst_shard_sizes.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.pst_pull.argtypes = [ctypes.c_void_p, u64p, i32p, ctypes.c_int64,
                             ctypes.c_int32, f32p]
    lib.pst_push.argtypes = [ctypes.c_void_p, u64p, f32p, ctypes.c_int64]
    lib.pst_shrink.restype = ctypes.c_int64
    lib.pst_shrink.argtypes = [ctypes.c_void_p]
    lib.pst_save_begin.restype = ctypes.c_int64
    lib.pst_save_begin.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.pst_save_fetch.argtypes = [ctypes.c_void_p, u64p, f32p]
    lib.pst_insert_full.argtypes = [ctypes.c_void_p, u64p, f32p, ctypes.c_int64]
    lib.pst_export.argtypes = [ctypes.c_void_p, u64p, ctypes.c_int64, f32p,
                               ctypes.POINTER(ctypes.c_uint8)]
    if hasattr(lib, "pst_export_create"):
        lib.pst_export_create.argtypes = [ctypes.c_void_p, u64p, i32p,
                                          ctypes.c_int64, f32p,
                                          ctypes.POINTER(ctypes.c_uint8)]
    if hasattr(lib, "pst_digest"):
        lib.pst_digest.restype = ctypes.c_uint64
        lib.pst_digest.argtypes = [ctypes.c_void_p]


def _f32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class NativeSparseTableEngine:
    """ctypes handle over the C++ MemorySparseTable engine
    (csrc/sparse_table.cc): shard-parallel pull/push with accessor + SGD
    math in native code. Raises RuntimeError if the native lib is
    unavailable — callers fall back to the Python shards."""

    def __init__(self, shard_num: int, accessor: str, acc_cfg,
                 seed: int) -> None:
        self._lib = load_native()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        if not getattr(self._lib, "_pst_configured", False):
            try:
                _configure_pst(self._lib)
            except AttributeError as e:  # stale .so without pst_* symbols
                raise RuntimeError(f"native library lacks sparse-table symbols: {e}")
            self._lib._pst_configured = True
        iparams, fparams = table_native_params(shard_num, accessor, acc_cfg,
                                               seed)
        self._h = self._lib.pst_create(_i32(iparams), _f32(fparams))
        self._save_lock = threading.Lock()  # begin/fetch must not interleave
        self.pull_dim = int(self._lib.pst_pull_dim(self._h))
        self.push_dim = int(self._lib.pst_push_dim(self._h))
        self.full_dim = int(self._lib.pst_full_dim(self._h))

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.pst_destroy(self._h)
            self._h = None

    def size(self) -> int:
        return int(self._lib.pst_size(self._h))

    def shard_sizes(self, shard_num: int) -> np.ndarray:
        out = np.empty(shard_num, np.int64)
        self._lib.pst_shard_sizes(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return out

    def pull(self, keys: np.ndarray, slots: Optional[np.ndarray], create: bool) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.uint64)
        out = np.empty((len(keys), self.pull_dim), np.float32)
        slots_arr = (np.ascontiguousarray(slots, np.int32)
                     if slots is not None else None)
        self._lib.pst_pull(self._h, _u64(keys),
                           _i32(slots_arr) if slots_arr is not None else None,
                           len(keys), 1 if create else 0, _f32(out))
        return out

    def push(self, keys: np.ndarray, push_values: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint64)
        push_values = np.ascontiguousarray(push_values, np.float32)
        self._lib.pst_push(self._h, _u64(keys), _f32(push_values), len(keys))

    def shrink(self) -> int:
        return int(self._lib.pst_shrink(self._h))

    def save_items(self, mode: int) -> Tuple[np.ndarray, np.ndarray]:
        """(keys [n], full rows [n, full_dim]) passing the mode filter."""
        with self._save_lock:
            n = int(self._lib.pst_save_begin(self._h, mode))
            keys = np.empty(n, np.uint64)
            values = np.empty((n, self.full_dim), np.float32)
            self._lib.pst_save_fetch(self._h, _u64(keys), _f32(values))
        return keys, values

    def export_full(self, keys: np.ndarray, create: bool = False,
                    slots: Optional[np.ndarray] = None) -> Tuple[np.ndarray, np.ndarray]:
        """(values [n, full_dim], found [n] bool). With ``create``,
        missing rows are inserted in the same shard traversal."""
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.empty((len(keys), self.full_dim), np.float32)
        found = np.empty(len(keys), np.uint8)
        fp = found.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        if create and hasattr(self._lib, "pst_export_create"):
            slots_arr = (np.ascontiguousarray(slots, np.int32)
                         if slots is not None else None)
            self._lib.pst_export_create(
                self._h, _u64(keys),
                _i32(slots_arr) if slots_arr is not None else None,
                len(keys), _f32(values), fp)
        else:
            if create:  # stale .so without the fused symbol: two passes
                self.pull(keys, slots, True)
            self._lib.pst_export(self._h, _u64(keys), len(keys), _f32(values), fp)
        return values, found.astype(bool)

    def insert_full(self, keys: np.ndarray, values: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.ascontiguousarray(values, np.float32)
        self._lib.pst_insert_full(self._h, _u64(keys), _f32(values), len(keys))

    def digest(self) -> int:
        """Order-independent content digest (pst_digest / pstpu::
        table_digest): equal across replicas holding identical rows."""
        if not hasattr(self._lib, "pst_digest"):
            raise RuntimeError("stale native library lacks pst_digest — "
                               "rebuild paddle_tpu/csrc")
        return int(self._lib.pst_digest(self._h))


# ---------------------------------------------------------------------------
# SSD (two-tier) sparse-table engine (csrc/ssd_table.cc)
# ---------------------------------------------------------------------------

# sst_create2 flag bits — mirror of the csrc flag contract
SST_FLAG_VALUE_F16 = 1       # value columns stored fp16 on disk
SST_FLAG_BLOCK_COMPRESS = 2  # log block-compressed (deflate + shared dict)

# sst_stats2 field layout — EXACT mirror of ssd_table.cc's SstStatField
# enum (graftlint wire_contract cross-checks name order and indices)
SST_STAT_FIELDS = {
    "hot_rows": 0,
    "cold_rows": 1,
    "disk_bytes": 2,
    "index_bytes": 3,
    "sketch_bytes": 4,
    "admit_checks": 5,
    "admit_rejects": 6,
    "admit_admitted": 7,
    "bg_compactions": 8,
    "bg_backlog": 9,
    "io_serve_bytes": 10,
    "io_bg_bytes": 11,
    "io_bg_wait_ms": 12,
    "open_block_bytes": 13,
}
SST_STAT_COUNT = 14

# block-compressed log record format — mirror of the csrc constants; the
# wire_contract pass fails tier-1 if either side drifts
SST_BLOCK_MAGIC = 0x4B4C4253  # 'SBLK' little-endian
SST_BLOCK_RECS = 128          # records per sealed block
SST_BLOCK_HDR_BYTES = 16      # u32 magic | u32 comp_len | u32 n_recs | u32 crc


def _configure_sst(lib: ctypes.CDLL) -> None:
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.sst_create.restype = ctypes.c_void_p
    lib.sst_create.argtypes = [i32p, f32p, ctypes.c_char_p]
    # flags bit 0 = fp16 value columns on disk (ssd_value_dtype="fp16");
    # a stale .so without the symbol raises through the AttributeError
    lib.sst_create2.restype = ctypes.c_void_p
    lib.sst_create2.argtypes = [i32p, f32p, ctypes.c_char_p, ctypes.c_int32]
    lib.sst_destroy.argtypes = [ctypes.c_void_p]
    for fn in ("sst_pull_dim", "sst_push_dim", "sst_full_dim"):
        getattr(lib, fn).restype = ctypes.c_int32
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.sst_size.restype = ctypes.c_int64
    lib.sst_size.argtypes = [ctypes.c_void_p]
    lib.sst_stats.argtypes = [ctypes.c_void_p, i64p]
    lib.sst_shard_sizes.argtypes = [ctypes.c_void_p, i64p]
    lib.sst_pull.argtypes = [ctypes.c_void_p, u64p, i32p, ctypes.c_int64,
                             ctypes.c_int32, f32p]
    lib.sst_push.argtypes = [ctypes.c_void_p, u64p, f32p, ctypes.c_int64]
    lib.sst_export.argtypes = [ctypes.c_void_p, u64p, i32p, ctypes.c_int64,
                               ctypes.c_int32, f32p, u8p]
    lib.sst_insert_full.argtypes = [ctypes.c_void_p, u64p, f32p, ctypes.c_int64]
    lib.sst_load_cold.argtypes = [ctypes.c_void_p, u64p, f32p, ctypes.c_int64]
    lib.sst_load_cold.restype = ctypes.c_int64
    lib.sst_spill.restype = ctypes.c_int64
    lib.sst_spill.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.sst_shrink.restype = ctypes.c_int64
    lib.sst_shrink.argtypes = [ctypes.c_void_p]
    lib.sst_compact.restype = ctypes.c_int64
    lib.sst_compact.argtypes = [ctypes.c_void_p]
    lib.sst_save_begin.restype = ctypes.c_int64
    lib.sst_save_begin.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.sst_save_fetch.argtypes = [ctypes.c_void_p, u64p, f32p]
    lib.sst_flush.argtypes = [ctypes.c_void_p]
    lib.sst_save_file.restype = ctypes.c_int64
    lib.sst_save_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int32, ctypes.c_int32]
    lib.sst_load_file.restype = ctypes.c_int64
    lib.sst_load_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int32]
    if hasattr(lib, "sst_digest"):
        lib.sst_digest.restype = ctypes.c_uint64
        lib.sst_digest.argtypes = [ctypes.c_void_p]
    # cold-tier scale surface (admission / compact index / io budget /
    # background compaction) — optional so a stale .so still loads for
    # the legacy paths; SsdTableEngine raises lazily where required
    if hasattr(lib, "sst_stats2"):
        lib.sst_stats2.restype = ctypes.c_int32
        lib.sst_stats2.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int32]
        lib.sst_admission_config.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                             ctypes.c_int32]
        lib.sst_io_budget.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_int64]
        lib.sst_bg_start.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.sst_bg_stop.argtypes = [ctypes.c_void_p]
        lib.sst_bg_step.restype = ctypes.c_int32
        lib.sst_bg_step.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                    ctypes.c_int32]
        lib.sst_compact_async.argtypes = [ctypes.c_void_p]


class SsdTableEngine:
    """ctypes handle over the two-tier C++ SSD table (csrc/ssd_table.cc):
    RAM hot tier + per-shard append-only log files with promote-on-access
    and cold spill. Same method surface as NativeSparseTableEngine plus
    spill/compact/stats/load_cold. Native-only — there is no Python
    fallback for the disk tier."""

    def __init__(self, shard_num: int, accessor: str, acc_cfg,
                 seed: int, path: str, value_f16: bool = False,
                 block_compress: bool = False) -> None:
        self._lib = load_native()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        if not getattr(self._lib, "_sst_configured", False):
            try:
                _configure_sst(self._lib)
            except AttributeError as e:  # stale .so without sst_* symbols
                raise RuntimeError(f"native library lacks ssd-table symbols: {e}")
            self._lib._sst_configured = True
        iparams, fparams = table_native_params(shard_num, accessor, acc_cfg,
                                               seed)
        flags = (SST_FLAG_VALUE_F16 if value_f16 else 0) | \
            (SST_FLAG_BLOCK_COMPRESS if block_compress else 0)
        self._h = self._lib.sst_create2(_i32(iparams), _f32(fparams),
                                        str(path).encode(), flags)
        if not self._h:
            raise RuntimeError(f"ssd table open failed at {path!r}")
        self._save_lock = threading.Lock()
        self._shard_num = shard_num
        self.pull_dim = int(self._lib.sst_pull_dim(self._h))
        self.push_dim = int(self._lib.sst_push_dim(self._h))
        self.full_dim = int(self._lib.sst_full_dim(self._h))

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.sst_destroy(self._h)
            self._h = None

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.sst_destroy(self._h)
            self._h = None

    def size(self) -> int:
        return int(self._lib.sst_size(self._h))

    def stats(self) -> Tuple[int, int, int]:
        """(hot rows, cold rows, disk bytes incl. log garbage)."""
        out = np.empty(3, np.int64)
        self._lib.sst_stats(self._h, out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)))
        return int(out[0]), int(out[1]), int(out[2])

    def shard_sizes(self, shard_num: int) -> np.ndarray:
        out = np.empty(shard_num, np.int64)
        self._lib.sst_shard_sizes(self._h, out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64)))
        return out

    def pull(self, keys: np.ndarray, slots, create: bool) -> np.ndarray:
        keys = np.ascontiguousarray(keys, np.uint64)
        out = np.empty((len(keys), self.pull_dim), np.float32)
        slots_arr = (np.ascontiguousarray(slots, np.int32)
                     if slots is not None else None)
        self._lib.sst_pull(self._h, _u64(keys),
                           _i32(slots_arr) if slots_arr is not None else None,
                           len(keys), 1 if create else 0, _f32(out))
        return out

    def push(self, keys: np.ndarray, push_values: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint64)
        push_values = np.ascontiguousarray(push_values, np.float32)
        self._lib.sst_push(self._h, _u64(keys), _f32(push_values), len(keys))

    def shrink(self) -> int:
        return int(self._lib.sst_shrink(self._h))

    def spill(self, budget: int) -> int:
        """Move the coldest hot rows to disk until ≤ budget stay hot."""
        return int(self._lib.sst_spill(self._h, ctypes.c_int64(budget)))

    def compact(self) -> int:
        """Rewrite the logs to live records only; returns disk bytes after.
        With the background compactor running this marks every shard
        forced and BLOCKS until the worker drains them."""
        return int(self._lib.sst_compact(self._h))

    def _require_scale_api(self) -> None:
        if not hasattr(self._lib, "sst_stats2"):
            raise RuntimeError("stale native library lacks cold-tier scale "
                               "symbols (sst_stats2…) — rebuild paddle_tpu/csrc")

    def stats2(self) -> Dict[str, int]:
        """Full cold-tier stat vector keyed by SST_STAT_FIELDS (admission
        hit/miss, index + sketch bytes, io-budget counters, compaction
        backlog…)."""
        self._require_scale_api()
        out = np.zeros(SST_STAT_COUNT, np.int64)
        n = int(self._lib.sst_stats2(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            SST_STAT_COUNT))
        return {name: int(out[i]) for name, i in SST_STAT_FIELDS.items()
                if i < n}

    def admission_config(self, threshold: int, sketch_kb: int = 64) -> None:
        """A key earns a durable row only after `threshold` observations
        (push misses); 0/1 disables the pre-filter. `sketch_kb` sizes the
        per-shard counting sketch."""
        self._require_scale_api()
        self._lib.sst_admission_config(self._h, int(threshold),
                                       int(sketch_kb))

    def io_budget(self, rate_bps: int, cap_bytes: int = 0) -> None:
        """Token-bucket disk budget shared by serve-class IO and the
        background compactor (serve never blocks; bg waits). 0 disables
        metering."""
        self._require_scale_api()
        self._lib.sst_io_budget(self._h, int(rate_bps), int(cap_bytes))

    def bg_start(self, interval_ms: int = 200) -> None:
        """Start the background compaction thread (sweeps the compaction
        policy every `interval_ms`, wakes early on explicit requests)."""
        self._require_scale_api()
        self._lib.sst_bg_start(self._h, int(interval_ms))

    def bg_stop(self) -> None:
        self._require_scale_api()
        self._lib.sst_bg_stop(self._h)

    def bg_step(self, shard: int, force: bool = False) -> int:
        """Run ONE background-compaction step inline (deterministic test
        hook; refused with -1 while the live thread runs)."""
        self._require_scale_api()
        return int(self._lib.sst_bg_step(self._h, int(shard),
                                         1 if force else 0))

    def compact_async(self) -> None:
        """Request a forced compaction of every shard WITHOUT waiting
        (the bg thread picks it up; no-op queue marker when bg is off)."""
        self._require_scale_api()
        self._lib.sst_compact_async(self._h)

    def flush(self) -> None:
        self._lib.sst_flush(self._h)

    def digest(self) -> int:
        """Order-independent content digest over BOTH tiers
        (csrc sst_digest: hot-tier table_digest + per-row hashes of the
        live disk records) — equal to a RAM replica's digest for the
        same logical rows. Was bound C-side since the HA PR but never
        exposed here; the job checkpoint's capture/restore digest
        verification needs it."""
        if not hasattr(self._lib, "sst_digest"):
            raise RuntimeError("stale native library lacks sst_digest — "
                               "rebuild paddle_tpu/csrc")
        return int(self._lib.sst_digest(self._h))

    def save_items(self, mode: int) -> Tuple[np.ndarray, np.ndarray]:
        with self._save_lock:
            n = int(self._lib.sst_save_begin(self._h, mode))
            keys = np.empty(n, np.uint64)
            values = np.empty((n, self.full_dim), np.float32)
            self._lib.sst_save_fetch(self._h, _u64(keys), _f32(values))
        return keys, values

    _FILE_FORMATS = {"text": 0, "gzip": 1, "raw": 2}

    def save_file(self, path: str, mode: int = 0,
                  fmt: str = "gzip") -> int:
        """STREAMING whole-table save to one file (csrc sst_save_file) —
        nothing staged in RAM, so populations beyond the begin/fetch
        snapshot's reach save fine. fmt: "text" | "gzip" (portable
        accessor text) | "raw" (fixed binary, ~6x faster)."""
        cnt = int(self._lib.sst_save_file(
            self._h, str(path).encode(), int(mode),
            self._FILE_FORMATS[fmt]))
        if cnt < 0:
            raise RuntimeError(f"streaming save to {path} failed (IO)")
        return cnt

    def load_file(self, path: str, fmt: str = "gzip") -> int:
        """Streaming load of a :meth:`save_file` file into the COLD
        tier (bounded batches)."""
        got = int(self._lib.sst_load_file(
            self._h, str(path).encode(), self._FILE_FORMATS[fmt]))
        if got < 0:
            raise RuntimeError(
                f"streaming load from {path} failed "
                f"(bad header/short load: {got})")
        return got

    def export_full(self, keys: np.ndarray, create: bool = False,
                    slots=None) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.empty((len(keys), self.full_dim), np.float32)
        found = np.empty(len(keys), np.uint8)
        slots_arr = (np.ascontiguousarray(slots, np.int32)
                     if slots is not None else None)
        self._lib.sst_export(self._h, _u64(keys),
                             _i32(slots_arr) if slots_arr is not None else None,
                             len(keys), 1 if create else 0, _f32(values),
                             found.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        return values, found.astype(bool)

    def insert_full(self, keys: np.ndarray, values: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.ascontiguousarray(values, np.float32)
        self._lib.sst_insert_full(self._h, _u64(keys), _f32(values), len(keys))

    def load_cold(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Bulk-load full rows straight into the disk tier. Raises on a
        short load (ENOSPC-style partial write — the engine truncates
        the partial slice so the log stays replay-consistent)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.ascontiguousarray(values, np.float32)
        loaded = self._lib.sst_load_cold(self._h, _u64(keys), _f32(values),
                                         len(keys))
        if loaded != len(keys):
            raise OSError(
                f"load_cold wrote only {loaded}/{len(keys)} rows "
                "(disk full or IO error; partial slice truncated)")


# ---------------------------------------------------------------------------
# Native data feed (csrc/data_feed.cc): multithreaded file -> channel
# ---------------------------------------------------------------------------


def _configure_dfd(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.dfd_create.restype = ctypes.c_void_p
    lib.dfd_create.argtypes = [ctypes.c_int, u8p, u8p, ctypes.c_char_p,
                               ctypes.c_int, ctypes.c_int]
    lib.dfd_destroy.argtypes = [ctypes.c_void_p]
    lib.dfd_next.restype = ctypes.c_int64
    lib.dfd_next.argtypes = [ctypes.c_void_p]
    lib.dfd_value_count.restype = ctypes.c_int64
    lib.dfd_value_count.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dfd_fetch.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p, i32p]
    lib.dfd_release.argtypes = [ctypes.c_void_p]
    lib.dfd_errors.restype = ctypes.c_int64
    lib.dfd_errors.argtypes = [ctypes.c_void_p]


class NativeDataFeed:
    """Channel-based multithreaded reader (data_feed.cc): iterate chunks
    of parsed slot columns as {name: (values, lengths)} dicts. Raises
    RuntimeError when the native lib is unavailable (callers fall back
    to the single-threaded Python path)."""

    def __init__(self, slots, files, num_threads: int = 4,
                 capacity: int = 8) -> None:
        self.slots = [(str(n), bool(f), bool(u)) for n, f, u in slots]
        self._lib = load_native()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        if not getattr(self._lib, "_dfd_configured", False):
            try:
                _configure_dfd(self._lib)
            except AttributeError as e:
                raise RuntimeError(f"native library lacks data-feed symbols: {e}")
            self._lib._dfd_configured = True
        is_float = np.asarray([f for _, f, _ in self.slots], np.uint8)
        used = np.asarray([u for _, _, u in self.slots], np.uint8)
        joined = "\n".join(files).encode()
        self._h = self._lib.dfd_create(
            len(self.slots),
            is_float.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            used.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            joined, num_threads, capacity)

    def __del__(self):
        self.close()

    def close(self) -> None:
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.dfd_destroy(self._h)
            self._h = None

    @property
    def errors(self) -> int:
        return int(self._lib.dfd_errors(self._h))

    def __iter__(self):
        while True:
            n = int(self._lib.dfd_next(self._h))
            if n < 0:
                return
            out = {}
            for s, (name, is_float, used) in enumerate(self.slots):
                if not used:
                    continue
                count = int(self._lib.dfd_value_count(self._h, s))
                values = np.empty(count, np.float32 if is_float else np.uint64)
                lengths = np.empty(n, np.int32)
                self._lib.dfd_fetch(
                    self._h, s, values.ctypes.data_as(ctypes.c_void_p),
                    lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
                out[name] = (values, lengths)
            self._lib.dfd_release(self._h)
            yield out
