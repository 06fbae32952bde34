"""ctypes loader for the native host runtime (native/host_runtime.cpp).

The image has no pybind11, so the boundary is plain C ABI + numpy
ctypeslib (environment constraint; ref for the role: the reference's
one native component is rocksdbjni, SURVEY.md §2.2).

The library is never committed: it is built with g++ on first use into
``native/build/`` under a file name that carries a digest of the
source bytes, the compile command and this CPU's feature flags.  The
command says ``-march=native``, so a library built on another CPU (or
from other source) has another name and is never loaded here — a
copied checkout rebuilds instead of dying on an illegal instruction.
When the build or the load fails, the compiler's stderr is logged once
at error level and `available()` is False.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import os
import platform
import subprocess
import time
from typing import Optional, Sequence

import numpy as np

from flink_tpu.runtime import tracing as _tracing

log = logging.getLogger(__name__)

_perf_ns = time.perf_counter_ns

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "host_runtime.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_COMPILE = ("g++", "-O3", "-march=native", "-shared", "-fPIC")

_lib: Optional[ctypes.CDLL] = None
#: the same library through a handle whose calls KEEP the interpreter
#: lock: the integer table's entry points (`NativeIntTable`), which a
#: foreign thread may read while its owner inserts
_gil_lib: Optional[ctypes.PyDLL] = None
_lib_path: Optional[str] = None
_load_error: Optional[str] = None


def cpu_feature_flags() -> str:
    """This CPU's feature flags as the kernel reports them — what
    ``-march=native`` compiles for."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.machine()


def artifact_name(src: bytes, compile_cmd: Sequence[str],
                  cpu_flags: str) -> str:
    """File name of the library built from exactly these inputs."""
    h = hashlib.sha256()
    for part in (src, "\0".join(compile_cmd).encode(), cpu_flags.encode()):
        h.update(len(part).to_bytes(8, "little"))
        h.update(part)
    return f"libhost_runtime-{h.hexdigest()[:20]}.so"


def _build(out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # compile beside the target, then rename: a concurrent process
    # sees the finished library or none
    tmp = f"{out_path}.{os.getpid()}.tmp"
    try:
        subprocess.run([*_COMPILE, "-o", tmp, _SRC], check=True,
                       capture_output=True, text=True)
        os.replace(tmp, out_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _ensure_loaded() -> Optional[ctypes.CDLL]:
    global _lib, _gil_lib, _lib_path, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    try:
        with open(_SRC, "rb") as f:
            src = f.read()
        path = os.path.join(
            _BUILD_DIR, artifact_name(src, _COMPILE, cpu_feature_flags()))
        if not os.path.exists(path):
            _build(path)
        lib = ctypes.CDLL(path)
        gil_lib = ctypes.PyDLL(path)
    except (OSError, subprocess.CalledProcessError) as e:
        _load_error = (getattr(e, "stderr", None) or str(e)).strip()
        log.error("native host runtime unavailable (%s): %s",
                  type(e).__name__, _load_error)
        return None
    _declare(lib)
    _declare_int_table(gil_lib)
    _lib, _gil_lib, _lib_path = lib, gil_lib, path
    return _lib


def _declare(lib: ctypes.CDLL) -> None:
    """argtypes/restype for every entry point."""
    u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    c = ctypes
    lib.ft_splitmix64.argtypes = [u64p, u64p, c.c_int64]
    lib.ft_key_groups.argtypes = [u64p, i32p, c.c_int64, c.c_int32,
                                  c.c_int32]
    lib.ft_heap_tumbling_baseline.argtypes = [
        u64p, u64p, f64p, c.c_int64, c.c_int, c.c_int, c.c_int64]
    lib.ft_heap_tumbling_baseline.restype = c.c_double
    lib.ft_heap_tumbling_meanmax_baseline.argtypes = [
        u64p, f64p, c.c_int64, c.c_int64]
    lib.ft_heap_tumbling_meanmax_baseline.restype = c.c_double
    lib.ft_heap_tumbling_lse_baseline.argtypes = [
        u64p, f32p, c.c_int64, c.c_int64]
    lib.ft_heap_tumbling_lse_baseline.restype = c.c_double
    lib.ft_argsort_u64.argtypes = [u64p, c.c_int64, i64p]
    lib.ft_cep_new.argtypes = [c.c_int64, c.c_int64, c.c_int64]
    lib.ft_cep_new.restype = c.c_void_p
    lib.ft_cep_free.argtypes = [c.c_void_p]
    lib.ft_cep_advance.argtypes = [
        c.c_void_p, u64p, u32p, i64p, c.c_int64, c.c_int64,
        i64p, i64p, c.c_int64]
    lib.ft_cep_advance.restype = c.c_int64
    lib.ft_cep_advance_seq.argtypes = [
        c.c_void_p, u64p, u32p, i64p, c.c_int64, c.c_int64,
        i64p, i64p, c.c_int64]
    lib.ft_cep_advance_seq.restype = c.c_int64
    lib.ft_cep_size.argtypes = [c.c_void_p]
    lib.ft_cep_size.restype = c.c_int64
    lib.ft_cep_min_ref.argtypes = [c.c_void_p]
    lib.ft_cep_min_ref.restype = c.c_int64
    lib.ft_cep_expire.argtypes = [c.c_void_p, c.c_int64]
    lib.ft_cep_export.argtypes = [c.c_void_p, u64p, u32p, i64p]
    lib.ft_cep_export.restype = c.c_int64
    lib.ft_cep_import.argtypes = [c.c_void_p, u64p, u32p, i64p,
                                  c.c_int64]
    lib.ft_cep_strict_baseline.argtypes = [
        u64p, f64p, i64p, c.c_int64, c.c_double, c.c_double,
        c.c_double, c.c_int64, c.c_int64, c.POINTER(c.c_int64)]
    lib.ft_cep_strict_baseline.restype = c.c_double
    lib.ft_cep_eval_masks.argtypes = [
        i64p, i64p, c.c_int64, f64p, f64p, c.c_int64, c.c_int64,
        u32p]
    lib.ft_cep_advance_prog.argtypes = [
        c.c_void_p, u64p, i64p, c.c_int64, c.c_int64,
        i64p, i64p, f64p, f64p, c.c_int64, c.c_int64,
        i64p, i64p, c.c_int64]
    lib.ft_cep_advance_prog.restype = c.c_int64
    lib.ft_cepr_new.argtypes = [c.c_int64, c.c_int64, c.c_int64,
                                c.c_int64]
    lib.ft_cepr_new.restype = c.c_void_p
    lib.ft_cepr_free.argtypes = [c.c_void_p]
    lib.ft_cepr_advance.argtypes = [
        c.c_void_p, u64p, u32p, i64p, c.c_int64, c.c_int64]
    lib.ft_cepr_advance.restype = c.c_int64
    lib.ft_cepr_advance_prog.argtypes = [
        c.c_void_p, u64p, i64p, c.c_int64, c.c_int64,
        i64p, i64p, f64p, f64p, c.c_int64]
    lib.ft_cepr_advance_prog.restype = c.c_int64
    lib.ft_cepr_matches.argtypes = [c.c_void_p, i64p, i64p]
    lib.ft_cepr_matches.restype = c.c_int64
    lib.ft_cepr_size.argtypes = [c.c_void_p]
    lib.ft_cepr_size.restype = c.c_int64
    lib.ft_cepr_expire.argtypes = [c.c_void_p, c.c_int64]
    lib.ft_cepr_min_ref.argtypes = [c.c_void_p]
    lib.ft_cepr_min_ref.restype = c.c_int64
    lib.ft_cepr_export_size.argtypes = [c.c_void_p]
    lib.ft_cepr_export_size.restype = c.c_int64
    lib.ft_cepr_export.argtypes = [c.c_void_p, i64p]
    lib.ft_cepr_export.restype = c.c_int64
    lib.ft_cepr_import.argtypes = [c.c_void_p, i64p, c.c_int64]
    lib.ft_cep_followed_baseline.argtypes = [
        u64p, f64p, i64p, c.c_int64, c.c_double, c.c_double,
        c.c_int64, c.c_int64, c.POINTER(c.c_int64)]
    lib.ft_cep_followed_baseline.restype = c.c_double
    lib.ft_fold_prep.argtypes = [u64p, c.c_int64, i64p, i64p, i64p,
                                 u64p]
    lib.ft_fold_prep.restype = c.c_int64
    lib.ft_group_cols.argtypes = [
        u64p, c.c_int64, c.c_int64, i64p,
        c.POINTER(c.c_void_p), c.POINTER(c.c_void_p), c.c_void_p,
        i64p, i64p, u64p]
    lib.ft_group_cols.restype = c.c_int64
    lib.ft_heap_windowed_hll_baseline.argtypes = [
        u64p, u64p, i64p, c.c_int64, c.c_int64, c.c_int, c.c_int64]
    lib.ft_heap_windowed_hll_baseline.restype = c.c_double
    lib.ft_heap_sliding_hist_baseline.argtypes = [
        u64p, f32p, i64p, c.c_int64, c.c_int64, c.c_int64, c.c_int,
        c.c_int64]
    lib.ft_heap_sliding_hist_baseline.restype = c.c_double
    lib.ft_heap_session_cm_baseline.argtypes = [
        u64p, u64p, i64p, c.c_int64, c.c_int64, c.c_int, c.c_int,
        c.c_int64]
    lib.ft_heap_session_cm_baseline.restype = c.c_double
    lib.ft_index_new.argtypes = [c.c_int64]
    lib.ft_index_new.restype = c.c_void_p
    lib.ft_index_free.argtypes = [c.c_void_p]
    lib.ft_index_size.argtypes = [c.c_void_p]
    lib.ft_index_size.restype = c.c_int64
    lib.ft_index_probe.argtypes = [c.c_void_p, u64p, c.c_int64, i64p,
                                   i64p]
    lib.ft_index_probe.restype = c.c_int64
    lib.ft_index_assign.argtypes = [c.c_void_p, i64p, c.c_int64, i64p]
    lib.ft_index_set.argtypes = [c.c_void_p, u64p, i64p, c.c_int64]
    lib.ft_index_export.argtypes = [c.c_void_p, u64p, i64p]
    lib.ft_index_export.restype = c.c_int64
    u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.ft_hll_make_cells.argtypes = [
        u64p, c.c_int64, c.c_int, u16p, u8p]
    lib.ft_hll_log_compact.argtypes = [
        u64p, u16p, u8p, c.c_int64, c.c_int,
        u64p, u16p, u8p, i32p, c.POINTER(c.c_int64)]
    lib.ft_hll_log_compact.restype = c.c_int64
    lib.ft_hll_log_fire.argtypes = [
        u64p, u16p, u8p, c.c_int64, c.c_int, u64p, f64p]
    lib.ft_hll_log_fire.restype = c.c_int64
    lib.ft_sum_log_fire.argtypes = [u64p, f64p, c.c_int64, u64p, f64p]
    lib.ft_sum_log_fire.restype = c.c_int64
    lib.ft_sumtab_new.argtypes = [c.c_int64]
    lib.ft_sumtab_new.restype = c.c_void_p
    lib.ft_sumtab_free.argtypes = [c.c_void_p]
    lib.ft_sumtab_size.argtypes = [c.c_void_p]
    lib.ft_sumtab_size.restype = c.c_int64
    lib.ft_sumtab_ingest.argtypes = [c.c_void_p, u64p, f64p,
                                     c.c_int64, c.c_int64]
    lib.ft_sumtab_ingest.restype = c.c_int64
    lib.ft_sumtab_export.argtypes = [c.c_void_p, u64p, f64p]
    lib.ft_sumtab_export.restype = c.c_int64
    lib.ft_qsketch_log_fire.argtypes = [
        u64p, u16p, c.c_int64, c.c_int, f64p, c.c_int,
        c.c_double, c.c_int64, c.c_double, u64p, f64p]
    lib.ft_qsketch_log_fire.restype = c.c_int64
    lib.ft_qsketch_log_fire2.argtypes = [
        u64p, u16p, u32p, c.c_int64, c.c_int, f64p, c.c_int,
        c.c_double, c.c_int64, c.c_double, u64p, f64p]
    lib.ft_qsketch_log_fire2.restype = c.c_int64
    lib.ft_qsketch_log_compact.argtypes = [
        u64p, u16p, u32p, c.c_int64, c.c_int, u64p, u16p, u32p]
    lib.ft_qsketch_log_compact.restype = c.c_int64
    lib.ft_session_log_fire.argtypes = [
        u64p, i64p, f32p, u64p, c.c_int64, c.c_int64, c.c_int64,
        c.c_int, c.c_int,
        u64p, i64p, i64p, f64p,
        u64p, i64p, f32p, u64p, c.POINTER(c.c_int64)]
    lib.ft_session_log_fire.restype = c.c_int64
    lib.ft_session_log_fire2.argtypes = [
        u64p, i64p, f32p, u64p, c.c_int64,
        u64p, i64p, f32p, u64p, c.c_int64,
        c.c_int64, c.c_int64, c.c_int, c.c_int,
        u64p, i64p, i64p, f64p,
        u64p, i64p, f32p, u64p, c.POINTER(c.c_int64)]
    lib.ft_session_log_fire2.restype = c.c_int64
    lib.ft_intern_new.argtypes = [c.c_int64]
    lib.ft_intern_new.restype = c.c_void_p
    lib.ft_intern_free.argtypes = [c.c_void_p]
    lib.ft_intern_size.argtypes = [c.c_void_p]
    lib.ft_intern_size.restype = c.c_int64
    lib.ft_intern_rows.argtypes = [c.c_void_p, u8p, c.c_int64,
                                   c.c_int64, c.c_int64, u64p, i64p]
    lib.ft_intern_rows.restype = c.c_int64
    lib.ft_heap_tumbling_baseline_str.argtypes = [
        u8p, c.c_int64, c.c_int64, c.c_int64, f64p, c.c_int64]
    lib.ft_heap_tumbling_baseline_str.restype = c.c_double
    lib.ft_wordsums_new.argtypes = []
    lib.ft_wordsums_new.restype = c.c_void_p
    lib.ft_wordsums_free.argtypes = [c.c_void_p]
    lib.ft_wordsums_count.argtypes = [c.c_void_p]
    lib.ft_wordsums_count.restype = c.c_int64
    lib.ft_wordsums_fire.argtypes = [c.c_void_p, i64p, f64p]
    lib.ft_wordsums_fire.restype = c.c_int64
    lib.ft_wordsums_load.argtypes = [c.c_void_p, i64p, f64p, c.c_int64]
    lib.ft_intern_sum.argtypes = [c.c_void_p, c.c_void_p, u8p,
                                  c.c_int64, c.c_int64, f64p,
                                  c.c_int64, c.c_int64, i64p]
    lib.ft_intern_sum.restype = c.c_int64
    lib.ft_interval_join_baseline.argtypes = [
        u64p, i64p, c.c_int64, u64p, i64p, c.c_int64,
        c.c_int64, c.c_int64, c.c_int64, c.POINTER(c.c_int64)]
    lib.ft_interval_join_baseline.restype = c.c_double
    lib.ft_ivjoin_new.argtypes = [c.c_int64, c.c_int64, c.c_int64]
    lib.ft_ivjoin_new.restype = c.c_void_p
    lib.ft_ivjoin_free.argtypes = [c.c_void_p]
    lib.ft_ivjoin_push.argtypes = [c.c_void_p, c.c_int64, u64p, i64p,
                                   c.c_int64]
    lib.ft_ivjoin_push.restype = c.c_int64
    lib.ft_ivjoin_pairs.argtypes = [c.c_void_p, i64p, i64p]
    lib.ft_ivjoin_pairs.restype = c.c_int64
    lib.ft_ivjoin_prune.argtypes = [c.c_void_p, c.c_int64]


def _declare_int_table(lib: ctypes.PyDLL) -> None:
    """argtypes/restype of the integer table's entry points."""
    c = ctypes
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.ft_itab_new.argtypes = [c.c_int64]
    lib.ft_itab_new.restype = c.c_void_p
    lib.ft_itab_free.argtypes = [c.c_void_p]
    lib.ft_itab_free.restype = None
    for name in ("ft_itab_size", "ft_itab_peak"):
        fn = getattr(lib, name)
        fn.argtypes = [c.c_void_p]
        fn.restype = c.c_int64
    lib.ft_itab_probe.argtypes = [c.c_void_p, i64p, c.c_int64, i64p, i64p]
    lib.ft_itab_probe.restype = c.c_int64
    lib.ft_itab_assign.argtypes = [c.c_void_p, i64p, c.c_int64, i64p,
                                   c.c_int64]
    lib.ft_itab_assign.restype = None
    for name in ("ft_itab_lookup", "ft_itab_take"):
        fn = getattr(lib, name)
        fn.argtypes = [c.c_void_p, i64p, c.c_int64, i64p]
        fn.restype = None
    lib.ft_itab_set.argtypes = [c.c_void_p, i64p, i64p, c.c_int64]
    lib.ft_itab_set.restype = None
    lib.ft_itab_export.argtypes = [c.c_void_p, i64p, i64p]
    lib.ft_itab_export.restype = c.c_int64
    for name in ("ft_itab_get1", "ft_itab_take1"):
        fn = getattr(lib, name)
        fn.argtypes = [c.c_void_p, c.c_int64]
        fn.restype = c.c_int64
    lib.ft_itab_set1.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.ft_itab_set1.restype = None


def available() -> bool:
    return _ensure_loaded() is not None


def load_error() -> Optional[str]:
    _ensure_loaded()
    return _load_error


def library_path() -> Optional[str]:
    """The library file this process loaded (None when unavailable)."""
    _ensure_loaded()
    return _lib_path


def _kernel(name: str):
    """Per-kernel dispatch counter + wall-time accounting around a
    host_runtime entry point.  Feeds runtime.tracing's kernel store
    (gauges under ``native.<name>``) and, when the tracer is enabled,
    emits a ``native.<name>`` span into the Chrome trace; a profiler
    trace shows the call as ``flink/native.<name>``.  The wrapper is
    transparent to the no-compiler degradation path — errors pass
    straight through."""
    label = "native." + name

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = _perf_ns()
            try:
                with _tracing.phase_annotation(label):
                    return fn(*args, **kwargs)
            finally:
                _tracing.record_kernel(name, t0, _perf_ns())
        return wrapper
    return deco


# ---- hot host-path kernels -------------------------------------------------

@_kernel("splitmix64")
def splitmix64(x: np.ndarray) -> np.ndarray:
    lib = _ensure_loaded()
    x = np.ascontiguousarray(x, np.uint64)
    out = np.empty_like(x)
    lib.ft_splitmix64(x, out, len(x))
    return out


@_kernel("key_groups")
def key_groups(kh: np.ndarray, max_parallelism: int,
               n_shards: int) -> np.ndarray:
    lib = _ensure_loaded()
    kh = np.ascontiguousarray(kh, np.uint64)
    out = np.empty(len(kh), np.int32)
    lib.ft_key_groups(kh, out, len(kh), max_parallelism, n_shards)
    return out


class NativeSlotIndex:
    """hash64 → dense slot via the C++ open-addressing table — the
    native drop-in for VectorizedSlotIndex.lookup_or_insert (same
    two-phase contract: new keys get slots from the caller's `alloc`,
    so the Python arena stays the one slot allocator)."""

    __slots__ = ("_h",)

    def __init__(self, capacity: int = 1 << 12):
        lib = _ensure_loaded()
        cap = 1 << max(4, (capacity - 1).bit_length())
        self._h = lib.ft_index_new(cap)

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_index_free(self._h)
            self._h = None

    @property
    def n(self) -> int:
        return _lib.ft_index_size(self._h)

    @_kernel("index.lookup_or_insert")
    def lookup_or_insert(self, batch_hashes: np.ndarray, alloc):
        h = np.ascontiguousarray(batch_hashes, np.uint64)
        n = len(h)
        slots = np.empty(n, np.int64)
        first_idx = np.empty(n, np.int64)
        n_new = _lib.ft_index_probe(self._h, h, n, slots, first_idx)
        first_idx = first_idx[:n_new]
        if n_new:
            new_slots = np.ascontiguousarray(alloc(n_new), np.int64)
            _lib.ft_index_assign(self._h, new_slots, n_new, slots)
        return slots, np.ones(n_new, bool), first_idx

    def set_bulk(self, hashes: np.ndarray, slots: np.ndarray) -> None:
        hashes = np.ascontiguousarray(hashes, np.uint64)
        slots = np.ascontiguousarray(slots, np.int64)
        _lib.ft_index_set(self._h, hashes, slots, len(hashes))

    def export(self):
        n = self.n
        hashes = np.empty(n, np.uint64)
        slots = np.empty(n, np.int64)
        k = _lib.ft_index_export(self._h, hashes, slots)
        return hashes[:k], slots[:k]


class NativeIntTable:
    """int64 key → int64 id (>= 0), the C++ `FtIntTable`: one
    namespace's table of the `tpu` state backend's slot index
    (`state/slot_index.py`).  Every int64 is a key; entries come back
    from `export` in the order they were entered, as a dict's would;
    a delete leaves nothing behind.  The caller owns the ids: `probe`
    enters the new keys of a batch and says which rows brought them,
    `assign` gives them their ids.  Key columns are int64, contiguous.

    Every call keeps the interpreter lock (the library's second
    handle, `ctypes.PyDLL`), so a call is as atomic as a dict's:
    `get` from a foreign thread never meets a table mid-growth.  No
    call is a `native.<kernel>` of its own in the books: its time is
    the slot phase's that made it (`state.add.slots`, ...), as the
    dict's was."""

    __slots__ = ("_h",)

    def __init__(self, room: int = 0) -> None:
        """`room`: entries it takes before it has to grow."""
        _ensure_loaded()
        self._h = _gil_lib.ft_itab_new(room)

    def __del__(self):
        if _gil_lib is not None and getattr(self, "_h", None):
            _gil_lib.ft_itab_free(self._h)
            self._h = None

    def __len__(self) -> int:
        return _gil_lib.ft_itab_size(self._h)

    def peak(self) -> int:
        """The most entries it ever held at once."""
        return _gil_lib.ft_itab_peak(self._h)

    def probe(self, keys: np.ndarray):
        """Phase one of probe-or-insert: ``(ids, first)``, `ids` the
        id of every row and `first` the rows that brought a key the
        table did not hold, in order; the rows of those keys read a
        negative id until `assign`, which follows at once."""
        n = len(keys)
        ids = np.empty(n, np.int64)
        first = np.empty(n, np.int64)
        m = _gil_lib.ft_itab_probe(self._h, keys, n, ids, first)
        return ids, first[:m]

    def assign(self, new_ids: np.ndarray, ids: np.ndarray) -> None:
        """Phase two: the k-th new key of the probe that returned
        `ids` gets ``new_ids[k]``, in the table and in `ids`."""
        _gil_lib.ft_itab_assign(self._h, new_ids, len(new_ids), ids,
                                len(ids))

    def lookup(self, keys: np.ndarray, take: bool = False) -> np.ndarray:
        """The ids of `keys`, -1 where the table has none; `take`
        removes what it finds (a key that comes twice is found once)."""
        ids = np.empty(len(keys), np.int64)
        (_gil_lib.ft_itab_take if take else _gil_lib.ft_itab_lookup)(
            self._h, keys, len(keys), ids)
        return ids

    def set(self, keys: np.ndarray, ids: np.ndarray) -> None:
        """``keys[i] → ids[i]``: new keys enter in row order, a key
        the table holds keeps its place."""
        _gil_lib.ft_itab_set(self._h, keys, ids, len(keys))

    def export(self):
        """``(keys, ids)`` of every entry, in the order of entry."""
        n = len(self)
        keys = np.empty(n, np.int64)
        ids = np.empty(n, np.int64)
        _gil_lib.ft_itab_export(self._h, keys, ids)
        return keys, ids

    def get(self, key: int) -> int:
        return _gil_lib.ft_itab_get1(self._h, key)

    def pop(self, key: int) -> int:
        return _gil_lib.ft_itab_take1(self._h, key)

    def put(self, key: int, id_: int) -> None:
        _gil_lib.ft_itab_set1(self._h, key, id_)


# ---- log-structured window engine kernels ---------------------------------

@_kernel("hll_log_compact")
def hll_log_compact(keys: np.ndarray, regs: np.ndarray, ranks: np.ndarray,
                    precision: int):
    """Sort a window's HLL cell log by key and dedup (reg)->max(rank).
    Returns (uniq cell keys, regs, ranks, per-key run ends)."""
    lib = _ensure_loaded()
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.uint64)
    regs = np.ascontiguousarray(regs, np.uint16)
    ranks = np.ascontiguousarray(ranks, np.uint8)
    ok = np.empty(n, np.uint64)
    orr = np.empty(n, np.uint16)
    ork = np.empty(n, np.uint8)
    ends = np.empty(n, np.int32)
    n_cells = ctypes.c_int64(0)
    n_keys = lib.ft_hll_log_compact(keys, regs, ranks, n, precision,
                                    ok, orr, ork, ends,
                                    ctypes.byref(n_cells))
    c = n_cells.value
    return ok[:c], orr[:c], ork[:c], ends[:n_keys]


@_kernel("hll_log_fire")
def hll_log_fire(keys: np.ndarray, regs: np.ndarray, ranks: np.ndarray,
                 precision: int):
    """Host-tier HLL fire over a window's cell log: per distinct key,
    the estimate (same math as sketches.HyperLogLogAggregate)."""
    lib = _ensure_loaded()
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.uint64)
    regs = np.ascontiguousarray(regs, np.uint16)
    ranks = np.ascontiguousarray(ranks, np.uint8)
    ok = np.empty(n, np.uint64)
    est = np.empty(n, np.float64)
    n_keys = lib.ft_hll_log_fire(keys, regs, ranks, n, precision, ok, est)
    return ok[:n_keys], est[:n_keys]


@_kernel("sum_log_fire")
def sum_log_fire(keys: np.ndarray, values: np.ndarray):
    """Per distinct key, the sum of its logged values (key-sorted)."""
    lib = _ensure_loaded()
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.uint64)
    values = np.ascontiguousarray(values, np.float64)
    ok = np.empty(n, np.uint64)
    s = np.empty(n, np.float64)
    n_keys = lib.ft_sum_log_fire(keys, values, n, ok, s)
    return ok[:n_keys], s[:n_keys]


class NativeSumTable:
    """Dense per-window sum accumulator (the hash-combiner tier):
    key -> running sum in an open-addressing C++ table.  Starts at
    `capacity` and grows geometrically — a window with few keys stays
    small."""

    __slots__ = ("_h", "capacity")

    def __init__(self, capacity: int = 1 << 12):
        lib = _ensure_loaded()
        self.capacity = 1 << max(4, (capacity - 1).bit_length())
        self._h = lib.ft_sumtab_new(self.capacity)

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_sumtab_free(self._h)
            self._h = None

    @property
    def n(self) -> int:
        return _lib.ft_sumtab_size(self._h)

    @_kernel("sum_table.ingest")
    def ingest(self, keys: np.ndarray, values: np.ndarray,
               max_distinct: int) -> int:
        """Accumulate; returns records consumed (< len(keys) when the
        distinct cap was hit — switch this window to log form)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.ascontiguousarray(values, np.float64)
        return _lib.ft_sumtab_ingest(self._h, keys, values, len(keys),
                                     max_distinct)

    def export(self):
        n = self.n
        keys = np.empty(n, np.uint64)
        sums = np.empty(n, np.float64)
        k = _lib.ft_sumtab_export(self._h, keys, sums)
        return keys[:k], sums[:k]


@_kernel("hll_make_cells")
def hll_make_cells(value_hashes: np.ndarray, precision: int):
    """(register u16, rank u8) cells from u64 value hashes — one C++
    pass (the ingest twin of HyperLogLogAggregate.compress_value_hash
    for precision <= 16)."""
    if precision > 16:
        # the numpy twin widens registers to uint32 above 16 bits;
        # this kernel's u16 output would silently alias them
        raise ValueError("hll_make_cells supports precision <= 16; "
                         "use compress_value_hash for wider registers")
    lib = _ensure_loaded()
    vh = np.ascontiguousarray(value_hashes, np.uint64)
    n = len(vh)
    regs = np.empty(n, np.uint16)
    ranks = np.empty(n, np.uint8)
    lib.ft_hll_make_cells(vh, n, precision, regs, ranks)
    return regs, ranks


@_kernel("qsketch_log_fire")
def qsketch_log_fire(keys: np.ndarray, buckets: np.ndarray,
                     n_buckets: int, quantiles, log_gamma: float,
                     offset: int, mid_corr: float, counts=None):
    """Per distinct key, the requested quantiles from its logged
    DDSketch buckets (key-sorted).  `counts` weights each cell
    (compacted logs); None = raw cells, weight 1.  Returns
    (keys, q [n_keys, n_q])."""
    lib = _ensure_loaded()
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.uint64)
    buckets = np.ascontiguousarray(buckets, np.uint16)
    q = np.ascontiguousarray(quantiles, np.float64)
    ok = np.empty(n, np.uint64)
    out = np.empty(n * len(q), np.float64)
    if counts is None:
        n_keys = lib.ft_qsketch_log_fire(keys, buckets, n, n_buckets,
                                         q, len(q), log_gamma, offset,
                                         mid_corr, ok, out)
    else:
        if n >= 1 << 32:
            # the weighted kernel carries the cell index in a 32-bit
            # field; beyond that it would silently gather wrong cells
            raise ValueError(
                "weighted quantile fire supports < 2^32 cells per "
                "window; lower compact_threshold so the log compacts")
        counts = np.ascontiguousarray(counts, np.uint32)
        n_keys = lib.ft_qsketch_log_fire2(keys, buckets, counts, n,
                                          n_buckets, q, len(q),
                                          log_gamma, offset, mid_corr,
                                          ok, out)
    return ok[:n_keys], out[:n_keys * len(q)].reshape(n_keys, len(q))


@_kernel("qsketch_log_compact")
def qsketch_log_compact(keys: np.ndarray, buckets: np.ndarray,
                        counts, n_buckets: int):
    """Collapse (key, bucket) duplicates into count cells — bounds a
    window's quantile log at keys x buckets cells.  `counts` weights
    existing cells (None = 1).  Returns (keys, buckets, counts)."""
    lib = _ensure_loaded()
    n = len(keys)
    keys = np.ascontiguousarray(keys, np.uint64)
    buckets = np.ascontiguousarray(buckets, np.uint16)
    if counts is None:
        counts = np.ones(n, np.uint32)
    else:
        counts = np.ascontiguousarray(counts, np.uint32)
    ok = np.empty(n, np.uint64)
    ob = np.empty(n, np.uint16)
    oc = np.empty(n, np.uint32)
    n_out = lib.ft_qsketch_log_compact(keys, buckets, counts, n,
                                       n_buckets, ok, ob, oc)
    return ok[:n_out].copy(), ob[:n_out].copy(), oc[:n_out].copy()


@_kernel("session_log_fire")
def session_log_fire(keys: np.ndarray, ts: np.ndarray, weights: np.ndarray,
                     vhs: np.ndarray, gap_ms: int, watermark: int,
                     depth: int, width: int, retained=None):
    """Close every session whose end-1 <= watermark: returns
    (closed keys, starts, ends, totals, retained (keys, ts, w, vh)).
    `retained` is the previous fire's retained tuple, in (key, ts)
    order — EXACTLY as this function returned it (the ordering is
    load-bearing: the kernel merges it as a key-major stream).  Pass
    it back verbatim; do not re-sort or merge it host-side."""
    lib = _ensure_loaded()
    keys = np.ascontiguousarray(keys, np.uint64)
    ts = np.ascontiguousarray(ts, np.int64)
    weights = np.ascontiguousarray(weights, np.float32)
    vhs = np.ascontiguousarray(vhs, np.uint64)
    if retained is None:
        pk = np.empty(0, np.uint64)
        pt = np.empty(0, np.int64)
        pw = np.empty(0, np.float32)
        pv = np.empty(0, np.uint64)
    else:
        pk = np.ascontiguousarray(retained[0], np.uint64)
        pt = np.ascontiguousarray(retained[1], np.int64)
        pw = np.ascontiguousarray(retained[2], np.float32)
        pv = np.ascontiguousarray(retained[3], np.uint64)
    n = len(keys) + len(pk)
    ok = np.empty(n, np.uint64)
    os_ = np.empty(n, np.int64)
    oe = np.empty(n, np.int64)
    ot = np.empty(n, np.float64)
    rk = np.empty(n, np.uint64)
    rt = np.empty(n, np.int64)
    rw = np.empty(n, np.float32)
    rv = np.empty(n, np.uint64)
    n_ret = ctypes.c_int64(0)
    n_closed = lib.ft_session_log_fire2(
        keys, ts, weights, vhs, len(keys),
        pk, pt, pw, pv, len(pk),
        gap_ms, watermark, depth, width,
        ok, os_, oe, ot, rk, rt, rw, rv, ctypes.byref(n_ret))
    r = n_ret.value
    return (ok[:n_closed], os_[:n_closed], oe[:n_closed], ot[:n_closed],
            (rk[:r].copy(), rt[:r].copy(), rw[:r].copy(), rv[:r].copy()))


# ---- compiled per-record heap baselines ------------------------------------

def _pow2_at_least(n: int) -> int:
    return 1 << max(4, (n - 1).bit_length())


def heap_tumbling_baseline(kh: np.ndarray, vh: Optional[np.ndarray],
                           values: Optional[np.ndarray], kind: str,
                           precision: int = 12,
                           capacity: Optional[int] = None) -> float:
    """Per-record heap-backend work, compiled.  kind: 'sum' | 'hll'.
    Returns records/second."""
    lib = _ensure_loaded()
    n = len(kh)
    kh = np.ascontiguousarray(kh, np.uint64)
    vh = (np.ascontiguousarray(vh, np.uint64) if vh is not None
          else np.zeros(1, np.uint64))
    values = (np.ascontiguousarray(values, np.float64) if values is not None
              else np.zeros(1, np.float64))
    cap = _pow2_at_least(capacity or 2 * n)
    elapsed = lib.ft_heap_tumbling_baseline(
        kh, vh, values, n, 1 if kind == "hll" else 0, precision, cap)
    return n / elapsed


def heap_tumbling_meanmax_baseline(kh: np.ndarray, values: np.ndarray,
                                   capacity: Optional[int] = None) -> float:
    """Per-record heap-backend work for a 3-field tuple accumulator
    (sum, count, max) — the generic-aggregate baseline.  Returns
    records/second."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or 2 * n)
    elapsed = lib.ft_heap_tumbling_meanmax_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(values, np.float64), n, cap)
    return n / elapsed


@_kernel("fold_prep")
def fold_prep(keys: np.ndarray):
    """Fused fire-path grouping for the generic-aggregate tier: stable
    radix argsort + segment detection + length-descending segment
    layout in one C++ pass.  Returns (order, seg_starts, seg_lens,
    ukeys) with segments in length-descending order."""
    lib = _ensure_loaded()
    keys = np.ascontiguousarray(keys, np.uint64)
    n = len(keys)
    order = np.empty(n, np.int64)
    seg_starts = np.empty(n, np.int64)
    seg_lens = np.empty(n, np.int64)
    ukeys = np.empty(n, np.uint64)
    n_seg = lib.ft_fold_prep(keys, n, order, seg_starts, seg_lens,
                             ukeys)
    return (order, seg_starts[:n_seg], seg_lens[:n_seg],
            ukeys[:n_seg])


@_kernel("group_cols")
def group_cols(keys: np.ndarray, cols=(), want_order: bool = True):
    """Small-domain (keys < 2^22) grouping with payload columns
    co-scattered in the same counting-sort pass: returns (order,
    scols, seg_starts, seg_lens, ukeys) with segments in
    length-descending order, or None when the key domain exceeds the
    histogram or a column isn't a 4/8-byte numeric.  order is None
    when not requested (the lifted fold doesn't need it once the
    columns are co-scattered)."""
    lib = _ensure_loaded()
    keys = np.ascontiguousarray(keys, np.uint64)
    n = len(keys)
    for col in cols:
        if col.dtype.itemsize not in (4, 8) or col.dtype.kind not in "fiu":
            return None
    cols = [np.ascontiguousarray(col) for col in cols]
    scols = [np.empty(n, col.dtype) for col in cols]
    nc = len(cols)
    elem = np.asarray([col.dtype.itemsize for col in cols], np.int64) \
        if nc else np.zeros(1, np.int64)
    src = (ctypes.c_void_p * max(nc, 1))(
        *[col.ctypes.data for col in cols] or [None])
    dst = (ctypes.c_void_p * max(nc, 1))(
        *[s.ctypes.data for s in scols] or [None])
    order = np.empty(n, np.int64) if want_order else None
    seg_starts = np.empty(n, np.int64)
    seg_lens = np.empty(n, np.int64)
    ukeys = np.empty(n, np.uint64)
    n_seg = lib.ft_group_cols(
        keys, n, nc, elem, src, dst,
        order.ctypes.data if want_order else None,
        seg_starts, seg_lens, ukeys)
    if n_seg < 0:
        return None
    return (order, scols, seg_starts[:n_seg], seg_lens[:n_seg],
            ukeys[:n_seg])


def heap_tumbling_lse_baseline(kh: np.ndarray, values: np.ndarray,
                               capacity=None) -> float:
    """Per-record heap-backend work for the streaming log-sum-exp
    aggregate (probe + stable (max, scaled-sum) update, two expf per
    record).  Returns records/second."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or 2 * n)
    elapsed = lib.ft_heap_tumbling_lse_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(values, np.float32), n, cap)
    return n / elapsed


class NativeCepState:
    """Persistent keyed strict-chain NFA state + fused batched advance
    (the C++ hot path of cep/vectorized.py): group-by-key, walk each
    key's run with carried state, emit match event ids.  Conditions
    arrive pre-evaluated as packed per-row stage bitmasks."""

    __slots__ = ("_h", "k", "_out")

    def __init__(self, k: int, within: int = -1,
                 capacity: int = 1 << 12):
        if k > 16:
            raise ValueError("at most 16 stages")
        lib = _ensure_loaded()
        cap = _pow2_at_least(capacity)
        self.k = k
        self._h = lib.ft_cep_new(k, within, cap)

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_cep_free(self._h)
            self._h = None

    @_kernel("cep.advance")
    def advance(self, kh: np.ndarray, mask_bits: np.ndarray,
                ts: np.ndarray, base_gid: int):
        """→ (match_refs [m, k] global event ids, match_rows [m]
        batch positions).  Variant selection: batches with high
        rows-per-key ratio amortize the grouped walk\'s sort; low-
        multiplicity batches probe per event instead (the sort would
        cost more than the state misses it saves)."""
        n = len(kh)
        # reuse the out buffers: a fresh 8B*k*n allocation per batch
        # page-faults more than the advance itself costs
        buf = getattr(self, "_out", None)
        if buf is None or len(buf[1]) < n:
            buf = (np.empty(n * self.k, np.int64),
                   np.empty(n, np.int64))
            self._out = buf
        out_refs, out_pos = buf
        known = max(_lib.ft_cep_size(self._h), 1)
        fn = (_lib.ft_cep_advance if n >= 8 * known
              else _lib.ft_cep_advance_seq)
        m = fn(self._h, np.ascontiguousarray(kh, np.uint64),
               np.ascontiguousarray(mask_bits, np.uint32),
               np.ascontiguousarray(ts, np.int64), n, base_gid,
               out_refs, out_pos, n)
        if m < 0:  # cannot happen with max_matches=n (<=1 match/row)
            raise RuntimeError("CEP match buffer overflow")
        return out_refs[:m * self.k].reshape(m, self.k), out_pos[:m]

    @_kernel("cep.advance_prog")
    def advance_prog(self, kh: np.ndarray, ts: np.ndarray,
                     base_gid: int, prog: np.ndarray,
                     stage_off: np.ndarray, consts: np.ndarray,
                     cols_flat: np.ndarray, ncols: int):
        """Fused advance with NATIVE condition evaluation: the
        predicate programs (cep/pattern.py compile_stage_programs)
        run columnwise in C++ and the mask bits never cross back
        into Python.  cols_flat is column-major float64
        [ncols * n]."""
        n = len(kh)
        buf = getattr(self, "_out", None)
        if buf is None or len(buf[1]) < n:
            buf = (np.empty(n * self.k, np.int64),
                   np.empty(n, np.int64))
            self._out = buf
        out_refs, out_pos = buf
        known = max(_lib.ft_cep_size(self._h), 1)
        use_seq = 0 if n >= 8 * known else 1
        m = _lib.ft_cep_advance_prog(
            self._h, np.ascontiguousarray(kh, np.uint64),
            np.ascontiguousarray(ts, np.int64), n, base_gid,
            np.ascontiguousarray(prog, np.int64),
            np.ascontiguousarray(stage_off, np.int64),
            np.ascontiguousarray(consts, np.float64),
            np.ascontiguousarray(cols_flat, np.float64), ncols,
            use_seq, out_refs, out_pos, n)
        if m < 0:  # cannot happen with max_matches=n (<=1 match/row)
            raise RuntimeError("CEP match buffer overflow")
        return out_refs[:m * self.k].reshape(m, self.k), out_pos[:m]

    @property
    def cold_w(self) -> int:
        k = self.k
        return (k - 1) + k * (k - 1) // 2

    def export(self):
        n = _lib.ft_cep_size(self._h)
        w = self.cold_w
        keys = np.empty(n, np.uint64)
        active = np.empty(n, np.uint32)
        cold = np.empty(n * w, np.int64)
        m = _lib.ft_cep_export(self._h, keys, active, cold)
        return keys[:m], active[:m], cold[:m * w].reshape(m, w)

    def min_ref(self) -> int:
        """Smallest event id an active run still references (log
        compaction watermark); 2^63-1 when no runs are active."""
        return _lib.ft_cep_min_ref(self._h)

    def import_(self, keys, active, cold) -> None:
        m = len(keys)
        _lib.ft_cep_import(
            self._h, np.ascontiguousarray(keys, np.uint64),
            np.ascontiguousarray(active, np.uint32),
            np.ascontiguousarray(
                np.asarray(cold).reshape(-1), np.int64), m)


def cep_expire(state: "NativeCepState", watermark: int) -> None:
    """Expire runs past the within() horizon (dormant-key sweep
    before log compaction)."""
    _lib.ft_cep_expire(state._h, watermark)


class NativeCepRuns:
    """Persistent keyed run-list NFA state for relaxed-contiguity
    (skip-till-next / followedBy) chains — the FULL run-list
    semantics of the scalar NFA, kept native.  A stage holds a
    linked list of waiting runs; advancement is all-or-nothing per
    event, so transitions splice whole lists and within()-expired
    runs form a lazily-truncated suffix.  Matches buffer internally
    (one event can complete many runs); fetch via the advance
    return."""

    __slots__ = ("_h", "k")

    def __init__(self, k: int, within: int = -1, strict_bits: int = 0,
                 capacity: int = 1 << 12):
        if k > 16:
            raise ValueError("at most 16 stages")
        lib = _ensure_loaded()
        self.k = k
        self._h = lib.ft_cepr_new(k, within, strict_bits,
                                  _pow2_at_least(capacity))

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_cepr_free(self._h)
            self._h = None

    def _fetch(self, m: int):
        if m == 0:
            return (np.empty((0, self.k), np.int64),
                    np.empty(0, np.int64))
        refs = np.empty(m * self.k, np.int64)
        pos = np.empty(m, np.int64)
        got = _lib.ft_cepr_matches(self._h, refs, pos)
        return refs[:got * self.k].reshape(got, self.k), pos[:got]

    @_kernel("cep_runs.advance")
    def advance(self, kh: np.ndarray, mask_bits: np.ndarray,
                ts: np.ndarray, base_gid: int):
        """→ (match_refs [m, k] global event ids, match_rows [m]
        batch positions)."""
        m = _lib.ft_cepr_advance(
            self._h, np.ascontiguousarray(kh, np.uint64),
            np.ascontiguousarray(mask_bits, np.uint32),
            np.ascontiguousarray(ts, np.int64), len(kh), base_gid)
        return self._fetch(m)

    @_kernel("cep_runs.advance_prog")
    def advance_prog(self, kh: np.ndarray, ts: np.ndarray,
                     base_gid: int, prog: np.ndarray,
                     stage_off: np.ndarray, consts: np.ndarray,
                     cols_flat: np.ndarray, ncols: int):
        """Fused advance with native predicate evaluation (see
        NativeCepState.advance_prog)."""
        m = _lib.ft_cepr_advance_prog(
            self._h, np.ascontiguousarray(kh, np.uint64),
            np.ascontiguousarray(ts, np.int64), len(kh), base_gid,
            np.ascontiguousarray(prog, np.int64),
            np.ascontiguousarray(stage_off, np.int64),
            np.ascontiguousarray(consts, np.float64),
            np.ascontiguousarray(cols_flat, np.float64), ncols)
        return self._fetch(m)

    def size(self) -> int:
        """Live-run count across all keys and stages."""
        return _lib.ft_cepr_size(self._h)

    def expire(self, watermark: int) -> None:
        """Truncate runs past the within() horizon (dormant-key
        sweep before log compaction)."""
        _lib.ft_cepr_expire(self._h, watermark)

    def min_ref(self) -> int:
        """Smallest event id a live run still references; 2^63-1
        when none."""
        return _lib.ft_cepr_min_ref(self._h)

    def export(self) -> np.ndarray:
        """Flat int64 checkpoint stream (lists serialized oldest-
        first so import's push-front rebuilds newest-first order)."""
        size = _lib.ft_cepr_export_size(self._h)
        buf = np.empty(max(size, 1), np.int64)
        w = _lib.ft_cepr_export(self._h, buf)
        return buf[:w].copy()

    def import_(self, buf: np.ndarray) -> None:
        buf = np.ascontiguousarray(buf, np.int64)
        _lib.ft_cepr_import(self._h, buf, len(buf))


def cep_followed_baseline(kh: np.ndarray, values: np.ndarray,
                          ts: np.ndarray, t0: float, t1: float,
                          within: int = -1, capacity=None):
    """Per-record skip-till-next (A followedBy B) run-list CEP over
    heap keyed state, compiled — the honest baseline for the
    cep_followed_by bench config.  Returns (records/second,
    match_count)."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or 2 * n)
    out = ctypes.c_int64(0)
    elapsed = lib.ft_cep_followed_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(values, np.float64),
        np.ascontiguousarray(ts, np.int64), n,
        t0, t1, within, cap, ctypes.byref(out))
    return n / elapsed, out.value


def cep_strict_baseline(kh: np.ndarray, values: np.ndarray,
                        ts: np.ndarray, t0: float, t1: float,
                        t2: float, within: int = -1,
                        capacity=None):
    """Per-record strict-chain CEP over heap keyed state, compiled.
    Returns (records/second, match_count)."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or 2 * n)
    out = ctypes.c_int64(0)
    elapsed = lib.ft_cep_strict_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(values, np.float64),
        np.ascontiguousarray(ts, np.int64), n,
        t0, t1, t2, within, cap, ctypes.byref(out))
    return n / elapsed, out.value


@_kernel("argsort_u64")
def argsort_u64(keys: np.ndarray) -> np.ndarray:
    """Stable argsort of a u64 column via the C++ adaptive radix sort
    (~5x numpy's stable comparison argsort at 8M 64-bit keys)."""
    lib = _ensure_loaded()
    keys = np.ascontiguousarray(keys, np.uint64)
    out = np.empty(len(keys), np.int64)
    lib.ft_argsort_u64(keys, len(keys), out)
    return out


def heap_windowed_hll_baseline(kh: np.ndarray, vh: np.ndarray,
                               ts: np.ndarray, window_ms: int,
                               precision: int = 12,
                               capacity: Optional[int] = None) -> float:
    """Multi-window tumbling HLL baseline (per-window state, cleanup on
    fire) — the north-star 10M-keyspace shape.  Returns records/s."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or n)
    elapsed = lib.ft_heap_windowed_hll_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(vh, np.uint64),
        np.ascontiguousarray(ts, np.int64),
        n, window_ms, precision, cap)
    return n / elapsed


def heap_sliding_hist_baseline(kh: np.ndarray, values: np.ndarray,
                               ts: np.ndarray, size_ms: int, slide_ms: int,
                               n_buckets: int = 128,
                               capacity: Optional[int] = None) -> float:
    """Sliding-window per-record work (one state update per overlapping
    window, as the reference does).  Returns records/second."""
    lib = _ensure_loaded()
    n = len(kh)
    overlap = size_ms // slide_ms
    cap = _pow2_at_least(capacity or 2 * n * overlap)
    elapsed = lib.ft_heap_sliding_hist_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(values, np.float32),
        np.ascontiguousarray(ts, np.int64),
        n, size_ms, slide_ms, n_buckets, cap)
    return n / elapsed


def heap_session_cm_baseline(kh: np.ndarray, vh: np.ndarray, ts: np.ndarray,
                             gap_ms: int, depth: int = 4, width: int = 2048,
                             capacity: Optional[int] = None) -> float:
    """Session-window Count-Min per-record work.  Returns records/s."""
    lib = _ensure_loaded()
    n = len(kh)
    cap = _pow2_at_least(capacity or 2 * n)
    elapsed = lib.ft_heap_session_cm_baseline(
        np.ascontiguousarray(kh, np.uint64),
        np.ascontiguousarray(vh, np.uint64),
        np.ascontiguousarray(ts, np.int64),
        n, gap_ms, depth, width, cap)
    return n / elapsed


# ---- string key interning ---------------------------------------------------

def _string_rows(arr: np.ndarray):
    """(raw row buffer u8 view, width_in_elems, elem_size) for a
    fixed-width numpy string array ('<U' UCS4 or '|S' bytes)."""
    if arr.dtype.kind == "U":
        elem = 4
    elif arr.dtype.kind == "S":
        elem = 1
    else:
        raise TypeError(f"not a fixed-width string array: {arr.dtype}")
    arr = np.ascontiguousarray(arr)
    width = arr.dtype.itemsize // elem
    if width == 0:  # zero-width dtype (all-empty strings)
        arr = arr.astype(f"{arr.dtype.kind}1")
        width = 1
    # explicit second dim: reshape(n, -1) rejects n=0
    rows = arr.view(np.uint8).reshape(len(arr), width * elem)
    return rows, width, elem


class NativeStringInterner:
    """String → dense uint64 id, content-exact, first-seen order.

    One C++ pass over numpy's contiguous fixed-width row buffer per
    batch — no per-string Python objects cross the boundary.  Dense
    first-seen ids make restore trivial: re-interning the id→string
    directory in order reproduces the same ids (round-2 verdict item
    2; the integer-keyed tiers take the ids from here)."""

    __slots__ = ("_h",)

    def __init__(self, capacity: int = 1 << 12):
        lib = _ensure_loaded()
        if lib is None:
            raise RuntimeError(f"native runtime required: {_load_error}")
        self._h = lib.ft_intern_new(_pow2_at_least(capacity))

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_intern_free(self._h)
            self._h = None

    @property
    def n(self) -> int:
        return _lib.ft_intern_size(self._h)

    @_kernel("interner.intern")
    def intern(self, arr: np.ndarray):
        """→ (ids uint64 [n], first_idx int64 [n_new]): dense ids per
        row; first_idx = batch row of each newly-seen string, in id
        order (append arr[first_idx] to the id→string directory)."""
        rows, width, elem = _string_rows(arr)
        n = len(arr)
        ids = np.empty(n, np.uint64)
        first_idx = np.empty(max(n, 1), np.int64)
        n_new = _lib.ft_intern_rows(self._h, rows, width, elem, n, ids,
                                    first_idx)
        return ids, first_idx[:n_new]


class NativeWordSums:
    """Dense per-window sum accumulator over interned word ids — the
    fused ingest half of the wordcount_str engine.  ``add`` interns
    and accumulates in one C++ pass (phase-split hashing + prefetched
    probe + direct-indexed add; see ft_intern_sum); ``fire`` exports
    (id, sum) for every touched id and resets."""

    __slots__ = ("_h",)

    def __init__(self):
        lib = _ensure_loaded()
        if lib is None:
            raise RuntimeError(f"native runtime required: {_load_error}")
        self._h = lib.ft_wordsums_new()

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_wordsums_free(self._h)
            self._h = None

    @_kernel("word_sums.add")
    def add(self, interner: "NativeStringInterner", words: np.ndarray,
            weights=None):
        """→ first_idx of newly-interned words (append words[first_idx]
        to the shared id→word directory)."""
        rows, width, elem = _string_rows(words)
        n = len(words)
        first_idx = np.empty(max(n, 1), np.int64)
        if weights is None:
            w = np.zeros(1, np.float64)
            has_w = 0
        else:
            w = np.ascontiguousarray(weights, np.float64)
            has_w = 1
        n_new = _lib.ft_intern_sum(interner._h, self._h, rows, width,
                                   elem, w, has_w, n, first_idx)
        return first_idx[:n_new]

    @property
    def touched(self) -> int:
        return _lib.ft_wordsums_count(self._h)

    @_kernel("word_sums.fire")
    def fire(self):
        """→ (ids int64, sums float64) of touched ids; resets."""
        k = self.touched
        ids = np.empty(k, np.int64)
        sums = np.empty(k, np.float64)
        _lib.ft_wordsums_fire(self._h, ids, sums)
        return ids, sums

    def load(self, ids: np.ndarray, sums: np.ndarray) -> None:
        _lib.ft_wordsums_load(
            self._h, np.ascontiguousarray(ids, np.int64),
            np.ascontiguousarray(sums, np.float64), len(ids))


class NativeIntervalJoin:
    """Batched time-bounded join core: per-key time-sorted buffers in
    C++, probed one BATCH at a time with slot resolution phase-split
    from the range searches (ILP the per-record baseline cannot get).
    push() returns pair GLOBAL ROW IDS per side — the caller owns the
    column storage and gathers vectorized."""

    __slots__ = ("_h",)

    def __init__(self, lower_ms: int, upper_ms: int,
                 capacity: int = 1 << 12):
        lib = _ensure_loaded()
        if lib is None:
            raise RuntimeError(f"native runtime required: {_load_error}")
        self._h = lib.ft_ivjoin_new(lower_ms, upper_ms,
                                    _pow2_at_least(capacity))

    def __del__(self):
        if _lib is not None and getattr(self, "_h", None):
            _lib.ft_ivjoin_free(self._h)
            self._h = None

    @_kernel("interval_join.push")
    def push(self, side: int, key_hashes: np.ndarray, ts: np.ndarray):
        """→ (left_rows, right_rows) int64 global row ids of the new
        pairs."""
        n_pairs = _lib.ft_ivjoin_push(
            self._h, side, np.ascontiguousarray(key_hashes, np.uint64),
            np.ascontiguousarray(ts, np.int64), len(key_hashes))
        l = np.empty(n_pairs, np.int64)
        r = np.empty(n_pairs, np.int64)
        _lib.ft_ivjoin_pairs(self._h, l, r)
        return l, r

    def prune(self, watermark: int) -> None:
        _lib.ft_ivjoin_prune(self._h, watermark)


def interval_join_baseline(kh_l: np.ndarray, ts_l: np.ndarray,
                           kh_r: np.ndarray, ts_r: np.ndarray,
                           lower_ms: int, upper_ms: int,
                           capacity: Optional[int] = None):
    """Per-record time-bounded stream join, compiled (the reference's
    keyed join ProcessFunction work).  Returns (records_per_sec,
    pair_count)."""
    import ctypes
    lib = _ensure_loaded()
    if lib is None:
        raise RuntimeError(f"native runtime required: {_load_error}")
    nl, nr = len(kh_l), len(kh_r)
    cap = _pow2_at_least(capacity or (nl + nr))
    pairs = ctypes.c_int64(0)
    elapsed = lib.ft_interval_join_baseline(
        np.ascontiguousarray(kh_l, np.uint64),
        np.ascontiguousarray(ts_l, np.int64), nl,
        np.ascontiguousarray(kh_r, np.uint64),
        np.ascontiguousarray(ts_r, np.int64), nr,
        lower_ms, upper_ms, cap, ctypes.byref(pairs))
    return (nl + nr) / elapsed, int(pairs.value)


def heap_tumbling_baseline_str(words: np.ndarray,
                               values: np.ndarray,
                               capacity: Optional[int] = None) -> float:
    """Per-record heap-backend work on STRING keys (hash + probe with
    string-equality verification + add, per record), compiled.
    Returns records/second."""
    lib = _ensure_loaded()
    rows, width, elem = _string_rows(words)
    n = len(words)
    cap = _pow2_at_least(capacity or 2 * n)
    elapsed = lib.ft_heap_tumbling_baseline_str(
        rows, width, elem, n,
        np.ascontiguousarray(values, np.float64), cap)
    return n / elapsed
