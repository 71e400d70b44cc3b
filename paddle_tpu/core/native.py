"""ctypes binding to the C++ runtime spine (native/ — SURVEY §2.4).

Loads libpaddle_tpu_native.so, building it with `make` on first use if the
checkout has a toolchain. Every consumer degrades gracefully to a pure-
Python fallback when the library is unavailable (`native.lib() is None`),
so the framework works on toolchain-less hosts; with the library, record
IO / reader queues / profiling / program framing run in C++.
"""

import ctypes
import fcntl
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_NAME = "libpaddle_tpu_native.so"

_lock = threading.Lock()
_lib = None
_tried = False


def _configure(lib):
    lib.ptpu_recordio_writer_open.restype = ctypes.c_void_p
    lib.ptpu_recordio_writer_open.argtypes = [ctypes.c_char_p,
                                              ctypes.c_uint64,
                                              ctypes.c_uint64]
    lib.ptpu_recordio_writer_open2.restype = ctypes.c_void_p
    lib.ptpu_recordio_writer_open2.argtypes = [ctypes.c_char_p,
                                               ctypes.c_uint64,
                                               ctypes.c_uint64,
                                               ctypes.c_uint32]
    lib.ptpu_recordio_writer_write.restype = ctypes.c_int
    lib.ptpu_recordio_writer_write.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p,
                                               ctypes.c_uint64]
    lib.ptpu_recordio_writer_close.restype = ctypes.c_int
    lib.ptpu_recordio_writer_close.argtypes = [ctypes.c_void_p]
    lib.ptpu_recordio_scanner_open.restype = ctypes.c_void_p
    lib.ptpu_recordio_scanner_open.argtypes = [ctypes.c_char_p]
    lib.ptpu_recordio_scanner_next.restype = ctypes.c_int64
    lib.ptpu_recordio_scanner_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    lib.ptpu_recordio_scanner_close.argtypes = [ctypes.c_void_p]

    lib.ptpu_queue_create.restype = ctypes.c_void_p
    lib.ptpu_queue_create.argtypes = [ctypes.c_uint64]
    lib.ptpu_queue_push.restype = ctypes.c_int
    lib.ptpu_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64, ctypes.c_int]
    lib.ptpu_queue_pop.restype = ctypes.c_int64
    lib.ptpu_queue_pop.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
        ctypes.c_int]
    lib.ptpu_queue_size.restype = ctypes.c_uint64
    lib.ptpu_queue_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_queue_close.argtypes = [ctypes.c_void_p]
    lib.ptpu_queue_destroy.argtypes = [ctypes.c_void_p]

    lib.ptpu_allocator_create.restype = ctypes.c_void_p
    lib.ptpu_allocator_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.ptpu_alloc.restype = ctypes.c_void_p
    lib.ptpu_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ptpu_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    for fn in ("ptpu_allocator_in_use", "ptpu_allocator_peak",
               "ptpu_allocator_alloc_count"):
        getattr(lib, fn).restype = ctypes.c_uint64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.ptpu_allocator_destroy.argtypes = [ctypes.c_void_p]

    lib.ptpu_prof_enable.argtypes = [ctypes.c_int]
    lib.ptpu_prof_enabled.restype = ctypes.c_int
    lib.ptpu_prof_push.argtypes = [ctypes.c_char_p]
    lib.ptpu_prof_mark.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                   ctypes.c_int64]
    lib.ptpu_prof_dump_chrome.restype = ctypes.c_int64
    lib.ptpu_prof_dump_chrome.argtypes = [ctypes.c_char_p]
    lib.ptpu_prof_stat_record.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.ptpu_prof_stat_count.restype = ctypes.c_int64
    lib.ptpu_prof_stat_count.argtypes = [ctypes.c_char_p]
    lib.ptpu_prof_stats_dump_json.restype = ctypes.c_int64
    lib.ptpu_prof_stats_dump_json.argtypes = [ctypes.c_char_p]

    lib.ptpu_program_seal.restype = ctypes.c_int64
    lib.ptpu_program_seal.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    lib.ptpu_program_unseal.restype = ctypes.c_int64
    lib.ptpu_program_unseal.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    lib.ptpu_buf_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
    lib.ptpu_crc32.restype = ctypes.c_uint32
    lib.ptpu_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.ptpu_version.restype = ctypes.c_char_p
    lib.ptpu_mslot_parse_file.restype = ctypes.c_void_p
    lib.ptpu_mslot_parse_file.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.ptpu_mslot_num_records.restype = ctypes.c_int64
    lib.ptpu_mslot_num_records.argtypes = [ctypes.c_void_p]
    lib.ptpu_mslot_bad_lines.restype = ctypes.c_int64
    lib.ptpu_mslot_bad_lines.argtypes = [ctypes.c_void_p]
    lib.ptpu_mslot_slot_total.restype = ctypes.c_int64
    lib.ptpu_mslot_slot_total.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_mslot_copy_int64.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_void_p]
    lib.ptpu_mslot_copy_float.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_void_p]
    lib.ptpu_mslot_copy_offsets.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                            ctypes.c_void_p]
    lib.ptpu_mslot_free.argtypes = [ctypes.c_void_p]

    lib.ptpu_tensor_frame.restype = ctypes.c_int64
    lib.ptpu_tensor_frame.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    lib.ptpu_tensor_unframe.restype = ctypes.c_int64
    lib.ptpu_tensor_unframe.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    return lib


def _build(native_dir):
    """Bring ``native_dir``'s library up to date, safely beside other
    processes doing the same (six test workers on a fresh checkout; the
    chip machine's first run). One builder at a time holds an exclusive
    ``flock`` on a file beside the Makefile; ``make`` writes to a
    temporary name and ``os.replace`` puts it in place, so whoever loads
    the library maps a whole file, and a process that already mapped the
    old one keeps its inode. A no-op when the library is newer than its
    sources; a rebuild when a stale (git-ignored) .so was carried along
    with newer committed sources. Returns False where there is no
    toolchain or the directory cannot be written."""
    tmp = ".%s.%d.tmp" % (_LIB_NAME, os.getpid())
    try:
        with open(os.path.join(native_dir, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)   # released when closed
            fresh = subprocess.run(["make", "-s", "-q", _LIB_NAME],
                                   cwd=native_dir, capture_output=True,
                                   timeout=120)
            if fresh.returncode == 0:
                return True
            subprocess.run(["make", "-s", "OUT=" + tmp, tmp],
                           cwd=native_dir, check=True,
                           capture_output=True, timeout=120)
            os.replace(os.path.join(native_dir, tmp),
                       os.path.join(native_dir, _LIB_NAME))
            return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        try:
            os.unlink(os.path.join(native_dir, tmp))
        except OSError:
            pass


def lib():
    """The loaded native library, or None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        native_dir = os.path.abspath(_NATIVE_DIR)
        if not _build(native_dir):
            return None
        try:
            _lib = _configure(ctypes.CDLL(
                os.path.join(native_dir, _LIB_NAME)))
        except OSError:
            _lib = None
        return _lib


def loaded():
    """The library if some caller's `lib()` already loaded it, else
    None. Never builds or loads: for hot paths (a tracing span's exit)
    that only have something to do once the library is in use."""
    return _lib


def _take_buf(l, ptr, n):
    data = ctypes.string_at(ptr, n)
    l.ptpu_buf_free(ptr)
    return data


def program_seal(payload: bytes) -> bytes:
    """Frame program bytes with magic/version/CRC (framework/version.h
    parity). Pure-python fallback mirrors the same layout."""
    l = lib()
    if l is not None:
        out = ctypes.POINTER(ctypes.c_char)()
        n = l.ptpu_program_seal(payload, len(payload), ctypes.byref(out))
        if n > 0:
            return _take_buf(l, out, n)
    import struct, zlib

    crc = zlib.crc32(payload) & 0xFFFFFFFF
    return (struct.pack("<IIQI", 0x50545047, 1, len(payload), crc) + payload)


def program_unseal(buf: bytes) -> bytes:
    l = lib()
    if l is not None:
        out = ctypes.POINTER(ctypes.c_char)()
        n = l.ptpu_program_unseal(buf, len(buf), ctypes.byref(out))
        if n >= 0:
            return _take_buf(l, out, n)
        raise ValueError("bad program file (code %d: magic/version/crc)" % n)
    import struct, zlib

    if len(buf) < 20:
        raise ValueError("bad program file: truncated")
    magic, version, plen, crc = struct.unpack("<IIQI", buf[:20])
    if magic != 0x50545047:
        raise ValueError("bad program file: magic")
    if version != 1:
        raise ValueError("unsupported program version %d" % version)
    payload = buf[20:20 + plen]
    if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
        raise ValueError("bad program file: CRC mismatch")
    return payload


class NativeQueue:
    """Bounded blocking queue of byte blobs backed by C++
    (LoDTensorBlockingQueue parity); falls back to queue.Queue."""

    def __init__(self, capacity):
        self._l = lib()
        if self._l is not None:
            self._q = self._l.ptpu_queue_create(capacity)
            self._py = None
        else:
            import queue as _queue

            self._py = _queue.Queue(maxsize=capacity)
            self._closed = False

    def push(self, data: bytes, timeout_ms=-1):
        if self._py is None:
            return self._l.ptpu_queue_push(self._q, data, len(data),
                                           timeout_ms) == 1
        self._py.put(data)
        return True

    def pop(self, timeout_ms=-1):
        """bytes, or None when closed and drained."""
        if self._py is None:
            out = ctypes.POINTER(ctypes.c_char)()
            n = self._l.ptpu_queue_pop(self._q, ctypes.byref(out), timeout_ms)
            if n == -2:
                return None
            if n < 0:
                raise TimeoutError("queue pop timed out")
            return _take_buf(self._l, out, n)
        item = self._py.get()
        return item  # None sentinel used for close

    def size(self):
        if self._py is None:
            return self._l.ptpu_queue_size(self._q)
        return self._py.qsize()

    def close(self):
        if self._py is None:
            self._l.ptpu_queue_close(self._q)
        else:
            self._py.put(None)

    def __del__(self):
        try:
            if getattr(self, "_py", True) is None and lib() is not None:
                self._l.ptpu_queue_destroy(self._q)
        except Exception:
            pass


class RecordIOWriter:
    """Chunked CRC'd record file writer (recordio/ parity).

    compressor: 0/None = plain, 1/'deflate' = zlib-compressed chunks
    (chunk.cc:79-96 parity; 'snappy' accepted as an alias — the wire
    format is ours, deflate is the bundled codec)."""

    _COMPRESSORS = {None: 0, "": 0, 0: 0, "none": 0,
                    1: 1, "deflate": 1, "snappy": 1}

    def __init__(self, path, max_chunk_records=1000,
                 max_chunk_bytes=1 << 20, compressor=None):
        self._l = lib()
        if self._l is None:
            raise RuntimeError("native library unavailable for RecordIO")
        key = compressor.lower() if isinstance(compressor, str) \
            else compressor
        if key not in self._COMPRESSORS:
            raise ValueError("unknown recordio compressor %r" % compressor)
        self._w = self._l.ptpu_recordio_writer_open2(
            path.encode(), max_chunk_records, max_chunk_bytes,
            self._COMPRESSORS[key])
        if not self._w:
            raise IOError("cannot open %s" % path)

    def write(self, record: bytes):
        if self._l.ptpu_recordio_writer_write(self._w, record,
                                              len(record)) != 0:
            raise IOError("recordio write failed")

    def close(self):
        if self._w:
            rc = self._l.ptpu_recordio_writer_close(self._w)
            self._w = None
            if rc != 0:
                # the final partial chunk flushes inside close: swallowing
                # a failure here would silently truncate the file's tail
                raise IOError("recordio close failed flushing the final "
                              "chunk (rc=%d)" % rc)


class RecordIOScanner:
    def __init__(self, path):
        self._l = lib()
        if self._l is None:
            raise RuntimeError("native library unavailable for RecordIO")
        self._s = self._l.ptpu_recordio_scanner_open(path.encode())
        if not self._s:
            raise IOError("cannot open %s" % path)

    def __iter__(self):
        out = ctypes.POINTER(ctypes.c_char)()
        while True:
            n = self._l.ptpu_recordio_scanner_next(self._s,
                                                   ctypes.byref(out))
            if n == -1:
                return
            if n == -2:
                raise IOError("corrupt recordio chunk (CRC)")
            yield ctypes.string_at(out, n)

    def close(self):
        if self._s:
            self._l.ptpu_recordio_scanner_close(self._s)
            self._s = None


def parse_multislot_columns(path, slot_types):
    """Columnar MultiSlot parse (data_feed.cc MultiSlotDataFeed parity):
    returns (slots, n_rec, bad_lines) where slots is a list of
    (values [total], offsets [n_rec+1]) per slot — NO per-record python
    objects, so batching stays vectorized numpy end to end."""
    import numpy as np

    type_codes = [0 if str(t).startswith(("int", "uint")) else 1
                  for t in slot_types]
    n_slots = len(type_codes)
    l = lib()
    if l is None:
        records, bad = _parse_multislot_py(path, type_codes)
        slots = []
        for s in range(n_slots):
            per = [np.asarray(r[s]).reshape(-1) for r in records]
            offs = np.zeros(len(records) + 1, np.int64)
            np.cumsum([p.shape[0] for p in per], out=offs[1:])
            vals = (np.concatenate(per) if per
                    else np.zeros(0, np.int64 if type_codes[s] == 0
                                  else np.float32))
            slots.append((vals, offs))
        return slots, len(records), bad

    arr = (ctypes.c_int * n_slots)(*type_codes)
    h = l.ptpu_mslot_parse_file(path.encode(), n_slots, arr)
    if not h:
        raise IOError("cannot open %s" % path)
    try:
        n_rec = l.ptpu_mslot_num_records(h)
        bad = l.ptpu_mslot_bad_lines(h)
        slots = []
        for s in range(n_slots):
            total = l.ptpu_mslot_slot_total(h, s)
            offs = np.empty(n_rec + 1, np.int64)
            l.ptpu_mslot_copy_offsets(h, s, offs.ctypes.data_as(
                ctypes.c_void_p))
            if type_codes[s] == 0:
                vals = np.empty(total, np.int64)
                l.ptpu_mslot_copy_int64(h, s, vals.ctypes.data_as(
                    ctypes.c_void_p))
            else:
                vals = np.empty(total, np.float32)
                l.ptpu_mslot_copy_float(h, s, vals.ctypes.data_as(
                    ctypes.c_void_p))
            slots.append((vals, offs))
        return slots, n_rec, int(bad)
    finally:
        l.ptpu_mslot_free(h)


def parse_multislot_file(path, slot_types):
    """Parse a MultiSlot text file with the C++ feed parser (data_feed.cc
    MultiSlotDataFeed parity). slot_types: list of "int64"/"uint64" or
    "float". Returns (records, bad_lines) where records is a list of
    per-record tuples of np arrays (one per slot). Falls back to a pure-
    Python parser when the native library is unavailable."""
    slots, n_rec, bad = parse_multislot_columns(path, slot_types)
    records = []
    for r in range(n_rec):
        records.append(tuple(
            vals[offs[r]:offs[r + 1]] for vals, offs in slots))
    return records, int(bad)


def _parse_multislot_py(path, type_codes):
    """Pure-Python fallback with identical semantics."""
    import numpy as np

    records, bad = [], 0
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            toks = line.split()
            rec, pos, ok = [], 0, True
            for code in type_codes:
                if pos >= len(toks):
                    ok = False
                    break
                try:
                    n = int(toks[pos])
                except ValueError:
                    ok = False
                    break
                if n < 0 or pos + 1 + n > len(toks):
                    ok = False
                    break
                chunk = toks[pos + 1:pos + 1 + n]
                try:
                    rec.append(np.asarray(
                        [int(t) for t in chunk], np.int64) if code == 0
                        else np.asarray([float(t) for t in chunk],
                                        np.float32))
                except (ValueError, OverflowError):
                    # OverflowError: uint64-range hash ids past int64 —
                    # rejected like the native parser's ERANGE check
                    ok = False
                    break
                pos += 1 + n
            if ok and pos == len(toks):
                records.append(tuple(rec))
            else:
                bad += 1
    return records, bad


# ---------------------------------------------------------------------------
# tensor wire framing (sendrecvop_utils.cc / variable_response.cc parity)
# ---------------------------------------------------------------------------

# dtype codes on the wire (stable enumeration; extend APPEND-ONLY)
_DTYPE_CODES = ["float32", "float64", "float16", "bfloat16", "int8",
                "int16", "int32", "int64", "uint8", "bool",
                "uint16", "uint32", "uint64", "complex64", "complex128"]
_TF_MAGIC = 0x50545446  # "PTTF"
_TF_MAX_NDIM = 16


def tensor_frame(arr) -> bytes:
    """Frame a numpy array for the pserver wire: dtype/shape header +
    CRC-checked payload, produced by the C++ runtime (tensor_frame.cc);
    pure-python fallback mirrors the layout bit-for-bit."""
    import numpy as np

    arr = np.asarray(arr)
    try:
        code = _DTYPE_CODES.index(str(arr.dtype))
    except ValueError:
        raise ValueError(
            "dtype %r has no tensor-wire code (supported: %s)"
            % (str(arr.dtype), ", ".join(_DTYPE_CODES)))
    if arr.ndim > _TF_MAX_NDIM:
        raise ValueError(
            "tensor rank %d exceeds the wire limit of %d"
            % (arr.ndim, _TF_MAX_NDIM))
    # shape BEFORE ascontiguousarray: it promotes 0-d to 1-d (ndmin=1)
    shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    payload = np.ascontiguousarray(arr).tobytes()
    l = lib()
    if l is not None:
        out = ctypes.POINTER(ctypes.c_char)()
        n = l.ptpu_tensor_frame(payload, len(payload), code, shape,
                                arr.ndim, ctypes.byref(out))
        if n > 0:
            return _take_buf(l, out, n)
    import struct, zlib

    crc = zlib.crc32(payload) & 0xFFFFFFFF
    return (struct.pack("<IBBH", _TF_MAGIC, code, arr.ndim, 0)
            + struct.pack("<%dq" % arr.ndim, *arr.shape)
            + struct.pack("<QI", len(payload), crc) + payload)


def tensor_unframe(buf: bytes):
    """Inverse of tensor_frame -> numpy array; raises on corruption."""
    import numpy as np

    l = lib()
    if l is not None:
        code = ctypes.c_int()
        ndim = ctypes.c_int()
        shape = (ctypes.c_int64 * 16)()
        out = ctypes.POINTER(ctypes.c_char)()
        n = l.ptpu_tensor_unframe(buf, len(buf), ctypes.byref(code), shape,
                                  ctypes.byref(ndim), ctypes.byref(out))
        if n < 0:
            raise ValueError("bad tensor frame (code %d: magic/ndim/crc)" % n)
        data = _take_buf(l, out, n)
        shp = tuple(shape[i] for i in range(ndim.value))
        return np.frombuffer(
            data, dtype=np.dtype(_DTYPE_CODES[code.value])).reshape(shp)
    import struct, zlib

    if len(buf) < 20:
        raise ValueError("bad tensor frame: truncated")
    magic, code, ndim, _ = struct.unpack("<IBBH", buf[:8])
    if magic != _TF_MAGIC:
        raise ValueError("bad tensor frame: magic")
    if ndim > _TF_MAX_NDIM or code >= len(_DTYPE_CODES):
        raise ValueError("bad tensor frame: ndim/dtype")
    off = 8 + 8 * ndim
    if len(buf) < off + 12:
        raise ValueError("bad tensor frame: truncated header")
    shp = struct.unpack_from("<%dq" % ndim, buf, 8)
    plen, crc = struct.unpack_from("<QI", buf, off)
    if plen > len(buf) - off - 12:
        raise ValueError("bad tensor frame: truncated payload")
    payload = buf[off + 12: off + 12 + plen]
    if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
        raise ValueError("bad tensor frame: CRC mismatch")
    import numpy as np

    return np.frombuffer(
        payload, dtype=np.dtype(_DTYPE_CODES[code])).reshape(shp)


# ---------------------------------------------------------------------------
# staging arena: buddy-allocator-backed host buffers for the feed path
# ---------------------------------------------------------------------------


class StagingArena:
    """Host staging pool for feed batches backed by the C++ buddy allocator
    (allocator.cc, buddy_allocator.h C19 parity). PyReader's double-buffer
    thread copies each batch into an arena-owned aligned buffer before
    jax.device_put, so the per-batch numpy heap churn disappears and H2D
    transfers read from stable, reused memory. Two rotating slots per
    (key, shape, dtype) keep the previous batch's buffer alive while its
    async copy completes (double-buffer depth 1). Degrades to plain numpy
    copies when the native library is unavailable."""

    def __init__(self, total_bytes=256 << 20, min_chunk_bytes=4096):
        self._lib = lib()
        self._h = None
        if self._lib is not None:
            self._h = self._lib.ptpu_allocator_create(total_bytes,
                                                      min_chunk_bytes)
        self._slots = {}
        self._flip = {}
        self._lock = threading.Lock()

    def stage(self, key, arr):
        """Copy `arr` into the arena; returns a numpy view over arena
        memory (or a plain copy without the native lib)."""
        import numpy as np

        arr = np.ascontiguousarray(arr)
        if self._h is None:
            return arr.copy()
        k = (key, arr.shape, arr.dtype.str)
        with self._lock:
            pair = self._slots.get(k)
            if pair is None:
                # evict this feed key's stale shapes, keeping the most
                # recent one as a spare (bucketed batches alternate a few
                # shapes; unbounded retention would pin the arena until
                # staging silently degraded to plain copies)
                stale = [k2 for k2 in self._slots
                         if k2[0] == key and k2 != k]
                for k2 in stale[:-1]:
                    self._release_slot(k2)
                stale = stale[-1:]

                def try_alloc():
                    ptrs, views = [], []
                    for _ in range(2):
                        ptr = self._lib.ptpu_alloc(self._h,
                                                   max(arr.nbytes, 1))
                        if not ptr:
                            for p in ptrs:
                                self._lib.ptpu_free(self._h, p)
                            return None
                        raw = (ctypes.c_char
                               * max(arr.nbytes, 1)).from_address(ptr)
                        views.append(np.frombuffer(
                            raw, dtype=arr.dtype).reshape(arr.shape))
                        ptrs.append(ptr)
                    return [views, ptrs, [None, None]]

                pair = try_alloc()
                if pair is None and stale:
                    # arena full: drop the spare too and retry once
                    self._release_slot(stale[0])
                    pair = try_alloc()
                if pair is None:
                    return arr.copy()
                self._slots[k] = pair
                self._flip[k] = 0
            i = self._flip[k]
            self._flip[k] = 1 - i
        views, _, pending = pair
        # the slot's previous batch may still be mid H2D copy (device_put
        # is async; PJRT reads the host buffer until the transfer lands):
        # wait for it before overwriting the arena memory
        if pending[i] is not None:
            try:
                pending[i].block_until_ready()
            except Exception:
                pass
            pending[i] = None
        view = views[i]
        view[...] = arr
        self._last_slot = (k, i)
        return view

    def note_transfer(self, staged_view, device_array):
        """Record the async device_put reading `staged_view`, so the slot
        is not overwritten until that transfer completes."""
        ks = getattr(self, "_last_slot", None)
        if ks is None:
            return
        k, i = ks
        pair = self._slots.get(k)
        if pair is not None and pair[0][i] is staged_view:
            pair[2][i] = device_array

    def _release_slot(self, k):
        """Free one slot pair (caller holds the lock): wait out in-flight
        transfers, then return the buffers to the buddy arena."""
        pair = self._slots.pop(k, None)
        self._flip.pop(k, None)
        if pair is None:
            return
        for dev in pair[2]:
            if dev is not None:
                try:
                    dev.block_until_ready()
                except Exception:
                    pass
        for p in pair[1]:
            self._lib.ptpu_free(self._h, p)

    def stats(self):
        if self._h is None:
            return {"in_use": 0, "peak": 0, "allocs": 0, "native": False}
        return {"in_use": int(self._lib.ptpu_allocator_in_use(self._h)),
                "peak": int(self._lib.ptpu_allocator_peak(self._h)),
                "allocs": int(self._lib.ptpu_allocator_alloc_count(self._h)),
                "native": True}

    def close(self):
        if self._h is not None:
            with self._lock:
                # drain in-flight transfers BEFORE freeing their host
                # buffers (PJRT reads them until the H2D copy lands),
                # then drop the views and the arena
                for k in list(self._slots):
                    self._release_slot(k)
            self._lib.ptpu_allocator_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
