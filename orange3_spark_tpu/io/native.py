"""ctypes binding for the native fastcsv engine (native/fastcsv.cpp).

The reference's ingest substrate is native too — Spark's JVM CSV reader into
Tungsten columnar memory (SURVEY.md §2b "Data ingest"; reconstructed, mount
empty). Here the C++ side produces row-major float32 chunks that go straight
into ``jax.device_put`` with P('data', None) sharding — no pandas hop, no
Python-level per-cell work. The library is compiled on first use with g++
(-O3 -pthread) and cached next to the source. ``read_csv_native`` falls back to the pyarrow
reader (io/readers.py) when no toolchain is available; the chunked
``NativeCsvReader`` API raises ``NativeUnavailable`` explicitly.

The same library holds the categorical half of a chunk's encode under the
'packed' cache codec (``hash_pack_rows``: impute, bucket hash, bit-pack in
one pass over the parsed rows); without the library it returns ``None`` and
the caller runs the numpy pair it is held bit-identical to. A process tries
the build ONCE: a failure is remembered, so the prefetch thread that asks
per chunk does not run g++ per chunk.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "fastcsv.cpp")
_LIB = os.path.join(os.path.dirname(_SRC), "_fastcsv.so")
_lock = threading.Lock()
_lib = None
_lib_error: str | None = None  # why this process has no library (tried once)


class NativeUnavailable(RuntimeError):
    pass


def _register_close(owner, lib, handle):
    """weakref.finalize hook closing a native handle exactly once.

    The callback captures only (lib, handle) — never the owner — and skips
    the native call when the interpreter is finalizing (the CDLL's function
    pointers may already be invalid there; leaking one FILE* at process exit
    is free, calling through a dead libffi trampoline is a SIGABRT)."""
    import weakref

    def _close(lib=lib, handle=handle):
        if not sys.is_finalizing():
            lib.fcsv_close(handle)

    return weakref.finalize(owner, _close)


def _build() -> str:
    # compile to a temp name, then atomically rename: another PROCESS (the
    # module lock is per-process) may race us to dlopen the final path and
    # must never see a half-written ELF
    tmp = f"{_LIB}.build.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, _LIB)
    except (subprocess.CalledProcessError, FileNotFoundError, OSError) as e:
        detail = getattr(e, "stderr", str(e))
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise NativeUnavailable(f"fastcsv build failed: {detail}") from e
    return _LIB


def tune_malloc() -> None:
    """Keep large allocations in the heap arena instead of per-call mmap.

    Every parsed chunk is a fresh ~40 MB numpy buffer; glibc serves those
    via mmap and unmaps on free, so each chunk pays full first-touch page
    faulting. Raising M_MMAP_THRESHOLD/M_TRIM_THRESHOLD keeps the pages
    resident across chunks — measured ~20% off the steady-state parse wall
    on the Criteo bench host.

    PROCESS-WIDE: after this call, any transient allocation up to 1 GB
    anywhere in the process stays in the heap and is never trimmed back to
    the OS. That is the right trade for a dedicated ingest/bench process
    and the wrong one to impose on a host application by side effect — so
    this is an explicit opt-in (bench.py/bench_suite.py call it; library
    loading does not)."""
    try:
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        libc.mallopt(-3, 1 << 30)  # M_MMAP_THRESHOLD
        libc.mallopt(-1, 1 << 30)  # M_TRIM_THRESHOLD
    except (OSError, AttributeError):
        pass  # non-glibc platform: skip


def get_lib():
    """Load (building if stale) the fastcsv shared library. One attempt a
    process: a failed build or load raises ``NativeUnavailable`` now and on
    every later call, without running the compiler again."""
    global _lib, _lib_error
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise NativeUnavailable(_lib_error)
        try:
            _lib = _load()
        except NativeUnavailable as e:
            _lib_error = str(e)
            raise
        return _lib


def _load():
    if (not os.path.exists(_LIB)
            or os.path.getmtime(_LIB) < os.path.getmtime(_SRC)):
        _build()
    try:
        lib = ctypes.CDLL(_LIB)
        _declare(lib)
    except (OSError, AttributeError) as e:
        # unloadable, or a build of an older source that lacks a symbol
        raise NativeUnavailable(f"fastcsv load failed: {e}") from e
    return lib


def _declare(lib) -> None:
    lib.fcsv_open.restype = ctypes.c_void_p
    lib.fcsv_open.argtypes = [ctypes.c_char_p, ctypes.c_char, ctypes.c_int]
    lib.fcsv_ncols.restype = ctypes.c_int
    lib.fcsv_ncols.argtypes = [ctypes.c_void_p]
    lib.fcsv_colname.restype = ctypes.c_char_p
    lib.fcsv_colname.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.fcsv_read_chunk.restype = ctypes.c_long
    lib.fcsv_read_chunk.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
        ctypes.c_int,
    ]
    lib.fcsv_close.restype = None
    lib.fcsv_close.argtypes = [ctypes.c_void_p]
    lib.fcsv_set_categorical.restype = ctypes.c_int
    lib.fcsv_set_categorical.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
    ]
    lib.fcsv_write.restype = ctypes.c_int
    lib.fcsv_write.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_float), ctypes.c_long,
        ctypes.c_int, ctypes.c_char_p, ctypes.c_char,
    ]
    lib.fcsv_hash_pack_rows.restype = ctypes.c_int
    lib.fcsv_hash_pack_rows.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_long, ctypes.c_long,
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint32,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_int,
    ]


def hash_pack_rows(cats: np.ndarray, salts: np.ndarray, n_dims: int,
                   bits: int, *, impute: bool) -> np.ndarray | None:
    """``pack_rows_np(hash_columns_np(cats, salts, n_dims), bits)`` — after
    ``NaN -> 0`` under ``impute`` — in ONE native pass over the rows of
    ``cats``: ``[N, C]`` f32 codes (any row stride: a column slice of the
    parsed chunk is read where it lies) -> ``[N, ceil(C*bits/32)]`` u32,
    the same bits as the numpy pair. ctypes releases the GIL for the call.

    Returns ``None`` where the native pass cannot run — no library, or a
    block that is not float32 rows with unit column stride — and the caller
    runs the numpy pair."""
    if n_dims & (n_dims - 1):
        raise ValueError(f"n_dims must be a power of two, got {n_dims}")
    if not 1 <= bits <= 31:
        raise ValueError(f"pack bit width must be in [1, 31], got {bits}")
    if (not isinstance(cats, np.ndarray) or cats.dtype != np.float32
            or cats.ndim != 2 or cats.strides[1] != 4
            or cats.strides[0] % 4 or cats.strides[0] < 0):
        return None
    try:
        lib = get_lib()
    except NativeUnavailable:
        return None
    n_rows, n_cat = cats.shape
    salts = np.ascontiguousarray(
        np.broadcast_to(np.asarray(salts, np.uint32), (n_cat,)))
    words = np.empty((n_rows, -(-(n_cat * bits) // 32)), np.uint32)
    u32_p = ctypes.POINTER(ctypes.c_uint32)
    rc = lib.fcsv_hash_pack_rows(
        cats.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        cats.strides[0] // 4, n_rows, n_cat, salts.ctypes.data_as(u32_p),
        n_dims - 1, bits, int(impute), words.ctypes.data_as(u32_p),
        words.shape[1],
    )
    if rc != 0:
        raise ValueError(
            f"fcsv_hash_pack_rows refused [{n_rows}, {n_cat}] at {bits} bits")
    return words


class NativeCsvReader:
    """Chunked reader over one CSV file.

    >>> r = NativeCsvReader("data.csv")
    >>> r.colnames
    ['a', 'b']
    >>> for chunk in r.chunks(1_000_000):   # f32 [rows, ncols] views
    ...     device_put(chunk, sharding)
    """

    def __init__(self, path: str, *, delimiter: str = ",", header: bool = True,
                 n_threads: int = 0,
                 categorical_cols: "tuple[int | str, ...]" = ()):
        """categorical_cols: column indices or header names whose cells are
        crc32&0xFFFFFF string-hashed at parse time (the native twin of
        ops.hashing.strings_to_u32) instead of float-parsed — real Criteo's
        hex-string categories flow through the native path losslessly."""
        self._lib = get_lib()
        self._h = self._lib.fcsv_open(
            path.encode(), delimiter.encode()[0:1] or b",", int(header)
        )
        if not self._h:
            raise FileNotFoundError(path)
        # GC safety net. weakref.finalize, NOT __del__: __del__ can fire from
        # an arbitrary thread's GC cycle or during interpreter finalization
        # when the ctypes CDLL machinery is already torn down — a native call
        # there is the classic 'Fatal Python error' SIGABRT at pytest exit.
        # finalize() runs before module teardown and is atomic/idempotent
        # against an explicit close().
        self._finalizer = _register_close(self, self._lib, self._h)
        self.n_threads = n_threads
        self.ncols = self._lib.fcsv_ncols(self._h)
        # strip RFC-4180 quoting from header names (pyarrow's writer quotes
        # all string fields by default): one matching outer pair only, with
        # doubled-quote unescaping — a name legitimately containing quotes
        # must survive
        def _unquote(s: str) -> str:
            if len(s) >= 2 and s[0] == '"' and s[-1] == '"':
                return s[1:-1].replace('""', '"')
            return s

        self.colnames = [
            _unquote(self._lib.fcsv_colname(self._h, j).decode())
            for j in range(self.ncols)
        ]
        self.categorical_cols: tuple[int, ...] = tuple(
            sorted(self._resolve_col(c) for c in categorical_cols)
        )
        for j in self.categorical_cols:
            self._lib.fcsv_set_categorical(self._h, j, 1)

    def _resolve_col(self, col: "int | str") -> int:
        if isinstance(col, str):
            if col not in self.colnames:
                raise ValueError(f"column {col!r} not in {self.colnames}")
            return self.colnames.index(col)
        j = int(col)
        if not 0 <= j < self.ncols:
            raise ValueError(f"column index {j} out of range 0..{self.ncols - 1}")
        return j

    def read_chunk(self, max_rows: int) -> np.ndarray | None:
        """Next up-to-max_rows rows as f32 [rows, ncols]; None at EOF."""
        if self._h is None:
            return None
        buf = np.empty((max_rows, self.ncols), dtype=np.float32)
        n = self._lib.fcsv_read_chunk(
            self._h,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            max_rows,
            self.n_threads,
        )
        if n == 0:
            return None
        if n == max_rows:
            return buf
        # short (trailing) chunk: copy so the view doesn't pin the full buffer
        return buf[:n].copy()

    def chunks(self, chunk_rows: int):
        while True:
            c = self.read_chunk(chunk_rows)
            if c is None:
                break
            yield c

    def read_all(self, chunk_rows: int = 1 << 20) -> np.ndarray:
        parts = list(self.chunks(chunk_rows))
        if not parts:
            return np.empty((0, self.ncols), dtype=np.float32)
        return np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]

    def close(self):
        # the finalizer owns the one-and-only-once native close; detach()
        # returns None on the second call, making close() idempotent and
        # race-free against GC
        if self._finalizer.detach() is not None:
            self._lib.fcsv_close(self._h)
        self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def write_csv_native(path: str, data: np.ndarray, names=None, *,
                     delimiter: str = ",") -> None:
    """f32 matrix -> CSV via the native writer (df.write.csv at scale;
    shortest-round-trip floats, ~an order of magnitude past np.savetxt).
    Raises NativeUnavailable when the engine can't build."""
    lib = get_lib()
    data = np.ascontiguousarray(data, dtype=np.float32)
    if data.ndim != 2:
        raise ValueError(f"data must be 2-D, got {data.shape}")
    header = b""
    if names is not None:
        if len(names) != data.shape[1]:
            raise ValueError(
                f"{len(names)} names for {data.shape[1]} columns"
            )
        quoted = []
        for n in names:
            s = str(n)
            if "\n" in s or "\r" in s:
                # '\n' is the transport separator to the native writer
                raise ValueError(f"column name {s!r} contains a newline")
            if delimiter in s or '"' in s:
                s = '"' + s.replace('"', '""') + '"'  # RFC-4180 quoting
            quoted.append(s)
        header = "\n".join(quoted).encode()
    rc = lib.fcsv_write(
        path.encode(), data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        data.shape[0], data.shape[1], header, delimiter.encode()[0:1] or b",",
    )
    if rc != 0:
        raise OSError(f"fcsv_write failed for {path!r}")


def read_csv_native(path: str, class_col: str = "", *, delimiter: str = ",",
                    header: bool = True, session=None, n_threads: int = 0):
    """Whole-file native read -> TpuTable (numeric columns only; string
    columns come through as NaN — use io.readers.read_csv for mixed schema).
    Falls back to the pyarrow reader when the native engine can't build."""
    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.table import TpuTable

    try:
        get_lib()
    except NativeUnavailable:
        from orange3_spark_tpu.io.readers import CsvReaderParams, read_csv

        return read_csv(
            params=CsvReaderParams(path=path, class_col=class_col,
                                   header=header, delimiter=delimiter),
            session=session,
        )
    with NativeCsvReader(path, delimiter=delimiter, header=header,
                         n_threads=n_threads) as r:
        data = r.read_all()
        names = list(r.colnames)
    if class_col:
        if class_col not in names:
            raise ValueError(f"class_col {class_col!r} not in {names}")
        ci = names.index(class_col)
        y = data[:, ci]
        keep = [j for j in range(len(names)) if j != ci]
        X = np.ascontiguousarray(data[:, keep])
        attrs = [ContinuousVariable(names[j]) for j in keep]
        domain = Domain(attrs, ContinuousVariable(class_col))
        return TpuTable.from_numpy(domain, X, y, session=session)
    domain = Domain([ContinuousVariable(n) for n in names])
    return TpuTable.from_numpy(domain, data, session=session)
