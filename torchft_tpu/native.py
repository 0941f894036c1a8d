"""ctypes bindings for the C++ runtime (``native/libtpuft.so``).

The reference ships its control plane as a Rust cdylib bound via pyo3
(``src/lib.rs``); torchft_tpu's equivalent is a C++ shared library bound via
ctypes (no pybind11 in the environment).  The C++ servers speak the exact
wire protocol of the Python implementations, so the Python clients
(``RpcClient`` subclasses) work against either — the classes here mirror the
Python servers' construction surface and are drop-in replacements.

The library is built on demand with ``make`` (g++ -O3) and stamped with a
hash of its sources, recipe and the host CPU's features; a binary whose
stamp does not match is rebuilt, never loaded.  If the toolchain or build
fails, ``available()`` returns False (``load_error()`` says why) and
``TORCHFT_TIER=auto`` callers fall back to the pure-Python implementations.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import platform
import queue
import subprocess
import threading
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu import knobs
from torchft_tpu.communicator import (
    Buffers,
    Communicator,
    CommunicatorAborted,
    CommunicatorError,
    ReduceOp,
    _sum_divisor,
)
from torchft_tpu.futures import TimerHandle, schedule_timeout
from torchft_tpu.obs.flight import FlightEvent, FlightRecorder
from torchft_tpu.obs import spans as obs_spans
from torchft_tpu.obs.spans import span as obs_span
from torchft_tpu.work import DummyWork, Work, failed_work

logger = logging.getLogger(__name__)

# Mirror of native/comm.h kMaxIovSegs — the max payload iovec segments the
# NATIVE side packs into one sendmsg/recvmsg syscall (the binding itself
# passes arbitrarily many buffers; batching happens in C).  Declared here
# so the ftlint native-mirror checker pins the two sides together.
_MAX_IOV_SEGS = 64


def _native_dir() -> str:
    """Directory holding the native build.  Native sources live beside the
    repo checkout; for installed wheels (where no sibling native/ exists)
    point TORCHFT_NATIVE_DIR at a sources/lib dir.  Read through the typed
    knob accessor at call time so monkeypatched tests behave like every
    other knob."""
    return knobs.get_str(
        "TORCHFT_NATIVE_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "native",
        ),
    )

_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None
_lib_lock = threading.Lock()

_DTYPE_CODES = {
    "float32": 0,
    "float64": 1,
    "int32": 2,
    "int64": 3,
    "bfloat16": 4,
    "uint8": 5,
    "int8": 6,
}
_OP_CODES = {ReduceOp.SUM: 0, ReduceOp.AVG: 0, ReduceOp.MAX: 1, ReduceOp.MIN: 2}


def _build_stamp(native_dir: str) -> str:
    """Hash of everything the binary depends on: the sources, the build
    recipe (Makefile plus the CXX/CXXFLAGS/LDFLAGS overrides it honours)
    and — because the recipe says ``-march=native`` — this host's CPU
    feature flags.  mtimes do not survive a copy of the tree, so freshness
    is decided by content."""
    digest = hashlib.sha256()
    for name in sorted(os.listdir(native_dir)):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            digest.update(name.encode())
            with open(os.path.join(native_dir, name), "rb") as f:
                digest.update(f.read())
    for var in ("CXX", "CXXFLAGS", "LDFLAGS"):
        digest.update(f"{var}={os.environ.get(var, '')}".encode())
    try:
        with open("/proc/cpuinfo") as f:
            cpu = next(
                (l for l in f if l.startswith(("flags", "Features"))), ""
            )
    except OSError:
        cpu = ""
    digest.update(platform.machine().encode() + cpu.encode())
    return digest.hexdigest()


def _stamped(lib_path: str, stamp: str) -> bool:
    try:
        with open(lib_path + ".stamp") as f:
            return os.path.exists(lib_path) and f.read() == stamp
    except OSError:
        return False


def _build_lib(native_dir: str, lib_path: str) -> None:
    """Make ``lib_path`` a build of THESE sources for THIS machine: a binary
    without a matching stamp (copied from another host, left over from an
    older checkout) is rebuilt, never loaded.  A binary whose stamp matches
    needs nothing written, so a read-only install loads it.  The lock file
    serializes concurrent first builds (a launcher starts its replicas
    together)."""
    stamp = _build_stamp(native_dir)
    if _stamped(lib_path, stamp):
        return
    try:
        lock = open(lib_path + ".lock", "w")
    except OSError as e:
        raise RuntimeError(
            f"{lib_path} is missing or was not built here from these "
            f"sources, and {native_dir} cannot be written ({e}); run `make "
            "-C native` as a user who can, or point TORCHFT_NATIVE_DIR at a "
            "writable copy of native/"
        ) from e
    with lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _stamped(lib_path, stamp):  # a peer built it while we waited
            return
        logger.info("building native runtime (make -B -C %s)", native_dir)
        try:
            subprocess.run(
                ["make", "-B", "-C", native_dir, "libtpuft.so"],
                check=True,
                capture_output=True,
                text=True,
                timeout=300,
            )
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build failed (rc {e.returncode}):\n{e.stderr}"
            ) from e
        with open(lib_path + ".stamp", "w") as f:
            f.write(stamp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_error
    with _lib_lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        try:
            native_dir = _native_dir()
            lib_path = os.path.join(native_dir, "libtpuft.so")
            _build_lib(native_dir, lib_path)
            lib = ctypes.CDLL(lib_path)
        except Exception as e:  # noqa: BLE001
            _lib_error = str(e)
            logger.warning("native runtime unavailable: %s", e)
            return None

        lib.tpuft_last_error.restype = ctypes.c_char_p
        lib.tpuft_store_new.restype = ctypes.c_void_p
        lib.tpuft_store_new.argtypes = [ctypes.c_char_p]
        lib.tpuft_store_port.argtypes = [ctypes.c_void_p]
        lib.tpuft_store_free.argtypes = [ctypes.c_void_p]
        lib.tpuft_lighthouse_new.restype = ctypes.c_void_p
        lib.tpuft_lighthouse_new.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_lighthouse_port.argtypes = [ctypes.c_void_p]
        lib.tpuft_lighthouse_free.argtypes = [ctypes.c_void_p]
        lib.tpuft_manager_new.restype = ctypes.c_void_p
        lib.tpuft_manager_new.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int64,
        ]
        lib.tpuft_manager_port.argtypes = [ctypes.c_void_p]
        lib.tpuft_manager_free.argtypes = [ctypes.c_void_p]
        lib.tpuft_comm_new.restype = ctypes.c_void_p
        lib.tpuft_comm_new.argtypes = [ctypes.c_double]
        lib.tpuft_comm_configure.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.tpuft_comm_allreduce.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int32,
            ctypes.c_int32,
        ]
        lib.tpuft_comm_allreduce_iov.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_alltoall_ptrs.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_lane_stats.restype = ctypes.c_uint64
        lib.tpuft_comm_lane_stats.argtypes = [
            ctypes.c_void_p,
            *[ctypes.POINTER(ctypes.c_uint64)] * 6,  # tx rx stalls | rx add tx ns
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),  # stripe floor
            ctypes.POINTER(ctypes.c_uint64),  # the op thread's five, ns
        ]
        # a round trip's rings as one call (comm.h RingSession)
        lib.tpuft_ring_session_open.restype = ctypes.c_void_p
        lib.tpuft_ring_session_open.argtypes = [
            ctypes.c_uint64, ctypes.c_int32, ctypes.c_uint64,
        ]
        lib.tpuft_ring_session_run.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.tpuft_ring_session_wait.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_double,
        ]
        lib.tpuft_ring_session_close.argtypes = [ctypes.c_void_p]
        lib.tpuft_ring_session_fail.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.tpuft_ring_session_times.restype = ctypes.c_uint64
        lib.tpuft_ring_session_times.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_uint64,
        ]
        lib.tpuft_ring_session_free.argtypes = [ctypes.c_void_p]
        # push through a handle that KEEPS the interpreter lock: the call is
        # a counter under a mutex and a notify, and a CDLL call would give the
        # lock up and wait for it again on the train thread, once a piece
        # (what one ring call cost the op thread, PERF.md section 6, PR 54)
        push = ctypes.PyDLL(lib_path).tpuft_ring_session_push
        push.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int32,
        ]
        lib.ring_session_push_holding_lock = push
        lib.tpuft_comm_reduce_scatter.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpuft_comm_broadcast.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int64,
        ]
        lib.tpuft_comm_send.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int64,
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_recv_alloc.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpuft_buffer_free.argtypes = [ctypes.c_void_p]
        lib.tpuft_comm_recv_into.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tpuft_comm_alltoall.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_allgather.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_flight_drain.restype = ctypes.c_uint64
        lib.tpuft_comm_flight_drain.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_uint64,
        ]
        lib.tpuft_comm_barrier.argtypes = [ctypes.c_void_p]
        lib.tpuft_comm_abort.argtypes = [ctypes.c_void_p]
        lib.tpuft_comm_free.argtypes = [ctypes.c_void_p]
        lib.tpuft_quantize_rowwise.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.tpuft_dequantize_rowwise.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
        ]
        lib.tpuft_reduce_rowwise.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    """Why the native runtime did not build or load (the compiler's or the
    loader's message), or None when it did."""
    _load()
    return _lib_error


# ---------------------------------------------------------------------------
# host quantization kernels (native/quant.h) — one-pass, multithreaded,
# -march=native; the numpy fallbacks in quantization.py make several full
# passes with temporaries and dominate the DCN quantized pipeline
# ---------------------------------------------------------------------------


def _check(lib: ctypes.CDLL, rc: int) -> None:
    if rc != 0:
        raise RuntimeError(lib.tpuft_last_error().decode())


def quantize_rowwise_native(
    flat: np.ndarray, row_size: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    lib = _load()
    if lib is None:
        return None
    flat = np.ascontiguousarray(flat, dtype=np.float32)
    n = flat.size
    rows = max(1, -(-n // row_size))
    q = np.empty((rows, row_size), np.int8)
    scales = np.empty(rows, np.float32)
    if n == 0:
        q[:] = 0
        scales[:] = 0.0
        return q, scales
    _check(
        lib,
        lib.tpuft_quantize_rowwise(
            _data_ptr(flat), n, row_size, _data_ptr(q), _data_ptr(scales)
        ),
    )
    return q, scales


def dequantize_rowwise_native(
    q: np.ndarray, scales: np.ndarray, n: int
) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    q = np.ascontiguousarray(q, dtype=np.int8)
    scales = np.ascontiguousarray(scales, dtype=np.float32)
    out = np.empty(n, np.float32)
    if n == 0:
        return out
    _check(
        lib,
        lib.tpuft_dequantize_rowwise(
            _data_ptr(q), _data_ptr(scales), n, q.shape[1], _data_ptr(out)
        ),
    )
    return out


def reduce_rowwise_native(
    qs: np.ndarray, scales: np.ndarray
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """qs int8 [w, rows, row_size], scales f32 [w, rows] → requantized
    (q [rows, row_size], scales [rows]) of the float32 sum."""
    lib = _load()
    if lib is None:
        return None
    qs = np.ascontiguousarray(qs, dtype=np.int8)
    scales = np.ascontiguousarray(scales, dtype=np.float32)
    w, rows, row_size = qs.shape
    q_out = np.empty((rows, row_size), np.int8)
    s_out = np.empty(rows, np.float32)
    _check(
        lib,
        lib.tpuft_reduce_rowwise(
            _data_ptr(qs),
            _data_ptr(scales),
            w,
            rows,
            row_size,
            _data_ptr(q_out),
            _data_ptr(s_out),
        ),
    )
    return q_out, s_out


def _data_ptr(arr: np.ndarray) -> ctypes.c_void_p:
    """C pointer to a contiguous array's data; extension dtypes (bfloat16)
    reject .ctypes on some views, so reinterpret through uint8."""
    if arr.dtype.name == "bfloat16":
        return arr.view(np.uint8).ctypes.data_as(ctypes.c_void_p)
    return arr.ctypes.data_as(ctypes.c_void_p)


def as_host_array(data) -> np.ndarray:
    """Zero-copy numpy view of any host buffer: numpy arrays pass through,
    buffer-protocol objects (bytes, bytearray, memoryview) come back as
    uint8 views, and dlpack-capable sources — JAX CPU arrays included —
    come back via ``np.from_dlpack`` (read-only, aliasing the producer's
    buffer).  Only objects that support none of those are copied
    (``np.asarray`` fallback).  The native data plane reads frames straight
    out of (and, for writable views, lands receives straight into) the
    returned array's memory — no staging copy."""
    if isinstance(data, np.ndarray):
        return data
    if hasattr(data, "__dlpack__"):
        # dlpack first for array-likes (jax CPU arrays): preserves
        # dtype/shape where the raw buffer protocol would flatten to bytes
        try:
            return np.from_dlpack(data)
        except (TypeError, AttributeError, RuntimeError, BufferError):
            pass
    try:
        # buffer protocol: bytes-like objects keep their exact bytes
        return np.frombuffer(memoryview(data).cast("B"), dtype=np.uint8)
    except TypeError:
        return np.asarray(data)


def _buffer_ptr(data) -> Tuple[ctypes.c_void_p, int, object]:
    """(pointer, nbytes, keepalive) into any contiguous buffer-protocol or
    dlpack-capable object with NO copy — the round-1 send path built
    intermediate ``bytes`` objects, a full-payload copy per hop.
    ``keepalive`` is the object that actually backs the pointer; the caller
    must pin it until the op is done (it is ``data`` itself unless a
    contiguity copy was required)."""
    arr = as_host_array(data)
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    return _data_ptr(arr), int(arr.nbytes), (arr, data)


def _last_error(lib: ctypes.CDLL) -> str:
    return lib.tpuft_last_error().decode("utf-8", "replace")


# ---------------------------------------------------------------------------
# server wrappers (drop-in for the Python servers)
# ---------------------------------------------------------------------------


class CppStoreServer:
    def __init__(self, bind: str = "0.0.0.0:0") -> None:
        lib = _load()
        assert lib is not None, "native runtime unavailable"
        self._lib = lib
        self._h = lib.tpuft_store_new(bind.encode())
        if not self._h:
            raise RuntimeError(f"store server failed: {_last_error(lib)}")

    @property
    def port(self) -> int:
        return self._lib.tpuft_store_port(self._h)

    def local_address(self) -> str:
        return f"127.0.0.1:{self.port}"

    def address(self) -> str:
        import socket

        return f"{socket.gethostname()}:{self.port}"

    def shutdown(self) -> None:
        if self._h:
            self._lib.tpuft_store_free(self._h)
            self._h = None


class CppLighthouseServer:
    def __init__(
        self,
        bind: str = "0.0.0.0:0",
        min_replicas: int = 1,
        join_timeout_ms: int = 100,
        quorum_tick_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
    ) -> None:
        lib = _load()
        assert lib is not None, "native runtime unavailable"
        self._lib = lib
        self._h = lib.tpuft_lighthouse_new(
            bind.encode(),
            min_replicas,
            join_timeout_ms,
            quorum_tick_ms,
            heartbeat_timeout_ms,
        )
        if not self._h:
            raise RuntimeError(f"lighthouse failed: {_last_error(lib)}")

    @property
    def port(self) -> int:
        return self._lib.tpuft_lighthouse_port(self._h)

    def local_address(self) -> str:
        return f"127.0.0.1:{self.port}"

    def address(self) -> str:
        import socket

        return f"{socket.gethostname()}:{self.port}"

    def shutdown(self) -> None:
        if self._h:
            self._lib.tpuft_lighthouse_free(self._h)
            self._h = None


class CppManagerServer:
    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        hostname: str = "",
        bind: str = "0.0.0.0:0",
        store_addr: str = "",
        world_size: int = 1,
        heartbeat_interval: float = 0.1,
        connect_timeout: float = 10.0,
        quorum_retries: int = 0,
        health_fn: Optional[object] = None,
        role: int = 0,
        warm_fn: Optional[object] = None,
        warm_step_fn: Optional[object] = None,
        capacity_fn: Optional[object] = None,
        metrics_fn: Optional[object] = None,
    ) -> None:
        import socket

        # health_fn (comm-health heartbeat summaries for straggler
        # detection) is accepted for construction parity with the Python
        # ManagerServer but unused: the C++ sidecar sends legacy
        # heartbeats, which the lighthouse treats as "no health report".
        # warm_fn (spare warm-snapshot serving) and warm_step_fn (the
        # beat-carried spare warm watermark) likewise: the C++ sidecar
        # cannot host a spare or feed one — spare roles require the Python
        # tier (Manager(role="spare") refuses a native server_cls).
        # capacity_fn (the wire-v5 degraded-capacity fraction) likewise:
        # the C++ sidecar always registers full-width — a degraded-mode
        # replica needs the Python control plane (Manager refuses to
        # complete a re-lower on a native server_cls; docs/operations.md
        # §16 has the fallback matrix entry).
        # metrics_fn (/metrics gauges) likewise: the C++ sidecar serves no
        # HTTP endpoint — scrape the lighthouse for fleet-level facts.
        del health_fn, warm_fn, warm_step_fn, capacity_fn, metrics_fn
        if role != 0:
            raise ValueError(
                "CppManagerServer does not support the SPARE role; use the "
                "Python tier for spare replicas"
            )
        lib = _load()
        assert lib is not None, "native runtime unavailable"
        self._lib = lib
        self.role = role  # attribute parity with ManagerServer
        self._hostname = hostname or socket.gethostname()
        self._h = lib.tpuft_manager_new(
            replica_id.encode(),
            lighthouse_addr.encode(),
            self._hostname.encode(),
            bind.encode(),
            store_addr.encode(),
            world_size,
            heartbeat_interval,
            connect_timeout,
            quorum_retries,
        )
        if not self._h:
            raise RuntimeError(f"manager server failed: {_last_error(lib)}")

    @property
    def port(self) -> int:
        return self._lib.tpuft_manager_port(self._h)

    def address(self) -> str:
        return f"{self._hostname}:{self.port}"

    def shutdown(self) -> None:
        if self._h:
            self._lib.tpuft_manager_free(self._h)
            self._h = None


# ---------------------------------------------------------------------------
# CppCommunicator
# ---------------------------------------------------------------------------


class RingSession:
    """A round trip's rings as ONE native call (``native/comm.h``
    ``RingSession``; :meth:`CppCommunicator.ring_session` opens it and puts
    its run on the op thread).  The caller pushes each flat, contiguous,
    writable array as it is ready, in the order every replica agrees on;
    piece k is rung in place exactly as ``allreduce(flat, SUM, in_place=True,
    divisor=)`` would ring it, after piece k-1 and never beside it; whoever
    needs piece k waits for it.  The first error fails its piece and every
    later one, and later pushes are no-ops.  A caller that may stop short of
    ``pieces`` pushes calls :meth:`close` (the op thread stays in the call
    until then)."""

    def __init__(self, lib: ctypes.CDLL, pieces: int, divisor: int) -> None:
        self._lib = lib
        self.pieces = pieces
        self._s = lib.tpuft_ring_session_open(pieces, _OP_CODES[ReduceOp.SUM], divisor)
        self._push = lib.ring_session_push_holding_lock
        self._kept: List[np.ndarray] = []  # what C holds pointers into, until the run ends
        self.pushed = 0
        self.work: Optional[Work] = None  # the op thread's run

    def push(self, flat: np.ndarray) -> bool:
        """Hand over the next piece; False where the session no longer takes
        one (it failed, was closed, or is full).  Neither blocks nor gives up
        the interpreter lock."""
        code = _DTYPE_CODES.get(flat.dtype.name)
        if code is None or not (flat.flags.c_contiguous and flat.flags.writeable):
            raise CommunicatorError(
                f"a session's piece is a contiguous writable array of a ring's dtype, not {flat.dtype.name}"
            )
        self._kept.append(flat)
        self.pushed += 1
        return bool(self._push(self._s, flat.ctypes.data, flat.nbytes, code))

    def wait(self, k: int, timeout: Optional[float] = None) -> None:
        """Return when piece ``k`` is rung (its array holds the result);
        raise what failed it, or ``TimeoutError``."""
        rc = self._lib.tpuft_ring_session_wait(
            self._s, k, -1.0 if timeout is None else timeout
        )
        if rc < 0:
            raise CommunicatorError(f"ring piece {k} failed: {_last_error(self._lib)}")
        if rc > 0:
            raise TimeoutError(f"ring piece {k} not rung after {timeout}s")

    def close(self) -> None:
        """No further push: the run ends after the pieces pushed so far."""
        self._lib.tpuft_ring_session_close(self._s)

    def fail(self, why: str) -> None:
        """The run failed or will never begin: every waiter wakes."""
        self._lib.tpuft_ring_session_fail(self._s, why.encode())

    def rung(self) -> int:
        """How many pieces have been rung."""
        return int(self._lib.tpuft_ring_session_times(self._s, None, None, 0))

    def times(self) -> List[Tuple[float, float]]:
        """(start, end) of each rung piece's ring on ``time.monotonic``."""
        t0 = (ctypes.c_double * self.pieces)()
        t1 = (ctypes.c_double * self.pieces)()
        n = int(self._lib.tpuft_ring_session_times(self._s, t0, t1, self.pieces))
        return [(t0[k], t1[k]) for k in range(min(n, self.pieces))]

    def __del__(self) -> None:
        # the run's closure and every waiter hold this object: nobody is
        # inside the C session any more
        if self._s:
            self._lib.tpuft_ring_session_free(self._s)
            self._s = None



class _SessionWatch:
    """The op watchdog's stand-in over a session's ONE call.  The C side
    gives every piece its own deadline, from when it is both pushed and at
    the head, so a slow landing never reads as a ring that hangs; this looks
    once a timeout and aborts the epoch only where a piece that was pushed
    and at the head at the LAST look is still not rung: C's deadline is then
    overdue, the call hangs where no deadline is checked, and the abort wakes
    it as it wakes an op.  ``cancel()`` when the call returns."""

    def __init__(self, session: RingSession, timeout_s: float, abort: Callable[[str], None]) -> None:
        self._session = session
        self._timeout_s = timeout_s
        self._abort = abort
        self._seen = (-1, 0)  # pieces rung and pushed at the last look
        self._cancelled = False
        self._handle = schedule_timeout(timeout_s, self._look)

    def _look(self) -> None:
        if self._cancelled:
            return
        now = (self._session.rung(), self._session.pushed)
        if now[0] == self._seen[0] and self._seen[1] > now[0]:
            self._abort(f"a session's piece {now[0]} was not rung {self._timeout_s}s after it was pushed and at the head")
            return
        self._seen = now
        self._handle = schedule_timeout(self._timeout_s, self._look)

    def cancel(self) -> None:
        self._cancelled = True
        self._handle.cancel()


class CppCommunicator(Communicator):
    """Data-plane communicator backed by the C++ runtime.

    Same semantics as :class:`torchft_tpu.communicator.TCPCommunicator`
    (repeatable configure, abort-poisons, per-op userspace timeouts) with the
    wire IO and reductions in native code.  ctypes releases the GIL during
    foreign calls, so the op thread never stalls Python.
    """

    def __init__(self, timeout_s: float = 60.0) -> None:
        lib = _load()
        assert lib is not None, "native runtime unavailable"
        self._lib = lib
        self._timeout_s = timeout_s
        self._h = lib.tpuft_comm_new(ctypes.c_double(timeout_s))
        self._rank = 0
        self._world_size = 1
        self._errored: Optional[Exception] = None
        self._lock = threading.Lock()
        self._epoch = 0
        self._ops: "queue.Queue[Optional[Tuple[Callable[[], object], Future]]]" = queue.Queue()
        self._op_thread: Optional[threading.Thread] = None
        # ops currently EXECUTING (the queue no longer holds them) — the
        # busy() probe's other half; own lock because overlapping old/new
        # epoch op threads can race the += / -= pair (same doctrine as
        # TCPCommunicator._inflight_ops)
        self._inflight_ops = 0
        self._inflight_lock = threading.Lock()
        # native ring calls made (``tpuft_comm_allreduce_iov`` and a
        # session's run): written on the op thread alone, never reset
        self._ring_calls = 0
        # flight recorder attachment point (set by the owning Manager):
        # epoch lifecycle records Python-side, and the C-side fixed-slot
        # ring drains into every dump via tpuft_comm_flight_drain
        self.flight: Optional[FlightRecorder] = None
        self._flight_registered = False

    # -- lifecycle ---------------------------------------------------------

    def configure(
        self,
        store_addr: str,
        replica_id: str,
        rank: int,
        world_size: int,
        quorum_id: int = 0,
        group_rank: int = 0,
        group_world_size: int = 1,
        global_ranks: Sequence[int] = (),
    ) -> None:
        with self._lock:
            drained = self._teardown_locked("superseded by reconfigure")
            self._epoch += 1
            epoch = self._epoch
            self._errored = None
            self._rank = rank
            self._world_size = world_size
        self._fail_drained(drained, "superseded by reconfigure")
        # the C configure blocks on rendezvous; run outside the lock
        rc = self._lib.tpuft_comm_configure(
            self._h, store_addr.encode(), rank, world_size
        )
        if rc != 0:
            err = CommunicatorError(
                f"configure failed: {_last_error(self._lib)}"
            )
            with self._lock:
                self._errored = err
            raise err
        with self._lock:
            if self._epoch != epoch:
                raise CommunicatorAborted("configure superseded")
            self._ops = queue.Queue()
            self._op_thread = threading.Thread(
                target=self._run_ops,
                args=(self._ops, epoch),
                name=f"tpuft_cppcomm_ops_{epoch}",
                daemon=True,
            )
            self._op_thread.start()
        if self.flight is not None:
            self.flight.set_comm_epoch(epoch)
            self.flight.record(
                FlightEvent.COMM_CONFIGURE,
                comm_epoch=epoch,
                quorum_id=quorum_id,
                rank=rank,
                world=world_size,
                tier="cpp",
            )
            if not self._flight_registered:
                # the C ring drains into every dump from here on
                self.flight.register_native_source(self)
                self._flight_registered = True
        logger.info(
            "cpp communicator configured: replica_id=%s rank=%d/%d quorum_id=%d",
            replica_id,
            rank,
            world_size,
            quorum_id,
        )

    def _teardown_locked(self, reason: str) -> List[Future]:
        """Returns the futures of the ops that never began: the caller fails
        them (:meth:`_fail_drained`) once it has let go of the lock."""
        # No join here: the op thread's error path takes self._lock, so
        # joining under the lock would deadlock.  The in-flight C op
        # observes the abort and errors out; the C layer parks superseded
        # fds in a graveyard until destruction, so the late-returning op can
        # never touch a recycled fd.
        if self._h:
            self._lib.tpuft_comm_abort(self._h)  # unblocks in-flight op
        drained: List[Future] = []
        try:
            while True:
                item = self._ops.get_nowait()
                if item is not None:
                    drained.append(item[1])
        except queue.Empty:
            pass
        if self._op_thread is not None:
            self._ops.put(None)
            self._op_thread = None
        return drained

    @staticmethod
    def _fail_drained(drained: List[Future], reason: str) -> None:
        # outside self._lock: a future's callbacks run here, and one of them
        # may come back for the lock (a ring session's error funnel dumps the
        # flight ring, whose drain of the C side takes it)
        for fut in drained:
            fut.set_exception(CommunicatorAborted(reason))

    def abort(self, reason: str = "aborted") -> None:
        with self._lock:
            newly_poisoned = self._errored is None
            if self._errored is None:
                self._errored = CommunicatorAborted(reason)
            drained = self._teardown_locked(reason)
            self._epoch += 1
        self._fail_drained(drained, reason)
        self._flight_poison(reason, newly_poisoned)
        logger.warning("cpp communicator aborted: %s", reason)

    def _flight_poison(self, reason: str, newly_poisoned: bool) -> None:
        """Record the epoch teardown (+ poison/dump when an error actually
        latched) — outside every lock, since a dump does file IO."""
        flight = self.flight
        if flight is None:
            return
        flight.record(FlightEvent.COMM_ABORT, reason=reason, tier="cpp")
        if newly_poisoned and reason != "shutdown":
            flight.record(
                FlightEvent.COMM_POISON, reason=reason, tier="cpp"
            )
            flight.maybe_dump("comm_poison")

    def _abort_if_epoch(self, epoch: int, reason: str) -> None:
        def _do() -> None:
            with self._lock:
                if self._epoch != epoch:
                    return
                newly_poisoned = self._errored is None
                if self._errored is None:
                    self._errored = CommunicatorAborted(reason)
                drained = self._teardown_locked(reason)
                self._epoch += 1
            self._fail_drained(drained, reason)
            self._flight_poison(reason, newly_poisoned)
            logger.warning("cpp communicator aborted: %s", reason)

        threading.Thread(target=_do, name="tpuft_cppcomm_abort", daemon=True).start()

    def errored(self) -> Optional[Exception]:
        return self._errored

    def shutdown(self) -> None:
        with self._lock:
            thread = self._op_thread
            drained = self._teardown_locked("shutdown")
            if self._errored is None:
                self._errored = CommunicatorAborted("shutdown")
            self._epoch += 1
        self._fail_drained(drained, "shutdown")
        # join OUTSIDE the lock (the op thread's error path takes it); the C
        # object must not be freed while an op thread is inside a C call
        if thread is not None:
            thread.join(timeout=15.0)
        with self._lock:
            if self._h and (thread is None or not thread.is_alive()):
                self._lib.tpuft_comm_free(self._h)
                self._h = None

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._world_size

    def set_timeout(self, timeout_s: float) -> None:
        self._timeout_s = timeout_s

    def busy(self) -> bool:
        """True while an op is executing or queued in the current epoch —
        the idle-priority yield probe (see TCPCommunicator.busy).  The
        queue alone is not enough: ``_run_ops`` dequeues BEFORE running,
        so a multi-second in-flight collective leaves the queue empty."""
        if self._inflight_ops > 0:
            return True
        ops = self._ops
        return ops is not None and not ops.empty()

    def _op_started(self) -> None:
        """Enter the in-flight window of :meth:`busy` — counter under its
        own lock; see TCPCommunicator._op_started (same doctrine, pinned by
        the same contention regression test)."""
        with self._inflight_lock:
            self._inflight_ops += 1

    def _op_finished(self) -> None:
        with self._inflight_lock:
            self._inflight_ops -= 1

    def lane_stats(self) -> Dict[str, object]:
        """Per-lane observability of the current epoch, tier-agnostic with
        :meth:`TCPCommunicator.lane_stats`: lane count, stripe floor,
        payload bytes sent/received per lane, and stall events (pacer
        denials / kernel would-block).  The gray-failure counters the
        Python tier additionally exports (reconnects/failovers/injected
        faults) report 0 — the native tier has no fault injection or
        in-epoch lane recovery yet.  Empty when unconfigured or
        single-member.

        Where the epoch's time went, in seconds since its configure,
        counted inside ``native/comm.h`` (``EpochIO``; always on).  A lane:
        ``lane_rx_s`` its thread inside ``::recv`` of a striped frame's
        header and payload (waiting for the peer AND the kernel's copy out
        of the socket, not told apart), ``lane_add_s`` inside the reduce's
        add, ``lane_tx_s`` its sender inside ``sendmsg`` and the pacing.
        The op thread, on the clock of ``tpuft/comm/op``:
        ``ring_reduce_s`` its wall time in the ring's reduce-scatter
        phase, ``ring_average_s`` in the stand-alone division pass (rings
        of one member alone: every other ring divides in its last reduce
        step's add, under ``lane_add_s``, and adds nothing here),
        ``ring_gather_s`` in the allgather phase, and
        ``ring_tail_s``, of the phases' steps, from its own part of a
        receive returning to the other lanes' parts and its own send having
        landed.  Lanes run beside each other, so a lane's seconds are a
        share of the phases' and the tail lies inside them.
        ``ring_wait_push_s``: a ring session's op thread waiting for the
        next piece to be pushed, between two rings and in neither phase;
        ``ring_calls``: the native ring calls the op thread has made in this
        communicator's life (one an ``allreduce``'s dtype group, one a
        session)."""
        with self._lock:
            if self._h is None or self._world_size <= 1:
                return {}
            cap = 64
            # tx rx stalls, then a lane's nanoseconds in recv, add, send
            lane = [(ctypes.c_uint64 * cap)() for _ in range(6)]
            ring_ns = (ctypes.c_uint64 * 5)()
            floor = ctypes.c_uint64()
            lanes = int(
                self._lib.tpuft_comm_lane_stats(
                    self._h, *lane, cap, ctypes.byref(floor), ring_ns
                )
            )
        if lanes <= 0:
            return {}
        n = min(lanes, cap)
        tx, rx, stalls, rx_ns, add_ns, tx_ns = ([int(c[i]) for i in range(n)] for c in lane)
        return {
            "lanes": lanes,
            "stripe_floor_bytes": int(floor.value),
            "lane_tx_bytes": tx,
            "lane_rx_bytes": rx,
            "lane_stalls": stalls,
            "lane_rx_s": [v / 1e9 for v in rx_ns],
            "lane_add_s": [v / 1e9 for v in add_ns],
            "lane_tx_s": [v / 1e9 for v in tx_ns],
            "ring_reduce_s": ring_ns[0] / 1e9,
            "ring_average_s": ring_ns[1] / 1e9,
            "ring_gather_s": ring_ns[2] / 1e9,
            "ring_tail_s": ring_ns[3] / 1e9,
            "ring_wait_push_s": ring_ns[4] / 1e9,
            "ring_calls": self._ring_calls,
            "lane_reconnects": 0,
            "lane_failovers": 0,
            "faults_injected": 0,
            "dead_lanes": 0,
        }

    def flight_drain(self) -> List[Dict[str, object]]:
        """Consume the C-side flight ring (``tpuft_comm_flight_drain``)
        into event dicts shaped like the Python recorder's, marked
        ``native``; repeated drains never duplicate events."""
        with self._lock:
            if self._h is None:
                return []
            cap = 256  # mirror of comm.h kFlightRingSlots
            seqs = (ctypes.c_uint64 * cap)()
            ts = (ctypes.c_double * cap)()
            evs = (ctypes.c_uint32 * cap)()
            a = (ctypes.c_int64 * cap)()
            b = (ctypes.c_int64 * cap)()
            n = int(
                self._lib.tpuft_comm_flight_drain(
                    self._h, seqs, ts, evs, a, b, cap
                )
            )
        out: List[Dict[str, object]] = []
        for i in range(n):
            ev = int(evs[i])
            out.append(
                {
                    "seq": int(seqs[i]),
                    "t": round(float(ts[i]), 6),
                    "ev": ev,
                    "name": (
                        FlightEvent(ev).name
                        if ev in FlightEvent._value2member_map_
                        else f"EV_{ev}"
                    ),
                    "a": int(a[i]),
                    "b": int(b[i]),
                    "native": True,
                }
            )
        return out

    # -- op machinery ------------------------------------------------------

    def _run_ops(self, ops: "queue.Queue", epoch: int) -> None:
        # k: this op is the k-th of its step (the peer's k-th is its twin);
        # a session's pieces count as the ops they stand for
        op_step, k = None, 0
        while True:
            item = ops.get()
            if item is None:
                return
            fn, fut = item
            if not fut.set_running_or_notify_cancel():
                continue
            session: Optional[RingSession] = getattr(fn, "session", None)
            timeout_s = self._timeout_s
            # a session's pieces each have the C side's deadline: one
            # deadline over the whole call would read a slow landing as a
            # ring that hangs, so the watch looks at the pieces' progress
            handle = _SessionWatch(
                session, timeout_s, lambda why: self._abort_if_epoch(epoch, why)
            ) if session is not None else schedule_timeout(
                timeout_s,
                lambda: self._abort_if_epoch(
                    epoch, f"op timed out after {timeout_s}s"
                ),
            )
            flight = self.flight
            obs_spans.bind(flight)  # this thread works for the replica
            step = flight.step if flight is not None else None
            op_step, k = step, (k + 1 if step == op_step else 0)
            self._op_started()
            try:
                if session is None:
                    with obs_span("tpuft/comm/op", epoch=epoch, k=k, tier="cpp"):
                        result = fn()
                else:
                    try:
                        with obs_span("tpuft/comm/session", epoch=epoch, k=k, pieces=session.pieces, tier="cpp"):
                            result = fn()
                    finally:
                        k += self._emit_pieces(session, epoch, k, step) - 1
            except BaseException as e:  # noqa: BLE001
                latched = False
                with self._lock:
                    if self._epoch == epoch and self._errored is None:
                        self._errored = (
                            e if isinstance(e, Exception) else RuntimeError(str(e))
                        )
                        latched = True
                if latched:
                    self._flight_poison(str(e), True)
                fut.set_exception(e)
            else:
                fut.set_result(result)
            finally:
                self._op_finished()
                handle.cancel()

    @staticmethod
    def _emit_pieces(session: RingSession, epoch: int, k: int, step: object) -> int:
        """One ``tpuft/comm/op`` span a rung piece, from the start and end
        the session kept in C (the op thread was inside ONE call and could
        open none): the spans a collective each has on the per-call path,
        ``k`` rising from the session's place in the step.  Returns how many
        ops the session stands for (one at least)."""
        if obs_spans.spans_enabled():
            for i, (t0, t1) in enumerate(session.times()):
                obs_spans.emit("tpuft/comm/op", t0, t1, epoch=epoch, k=k + i, step=step, tier="cpp")
        return max(1, session.pieces)

    def _submit(self, fn: Callable[[], object]) -> Work:
        with self._lock:
            if self._errored is not None:
                fut: Future = Future()
                fut.set_exception(self._errored)
                return Work(fut)
            if self._op_thread is None:
                fut = Future()
                fut.set_exception(CommunicatorError("communicator not configured"))
                return Work(fut)
            fut = Future()
            self._ops.put((fn, fut))
            return Work(fut)

    def _check(self, rc: int, what: str) -> None:
        if rc != 0:
            raise CommunicatorError(f"{what} failed: {_last_error(self._lib)}")

    # -- collectives -------------------------------------------------------

    @staticmethod
    def _as_list(buffers: Buffers) -> List[np.ndarray]:
        """Host views of the input buffers — numpy passes through, dlpack /
        buffer-protocol sources (JAX CPU arrays included) come back as
        zero-copy views (:func:`as_host_array`)."""
        if isinstance(buffers, np.ndarray):
            return [buffers]
        return [as_host_array(b) for b in buffers]

    def allreduce(
        self,
        buffers: Buffers,
        op: ReduceOp = ReduceOp.SUM,
        in_place: bool = False,
        divisor: Optional[int] = None,
    ) -> Work:
        arrays = self._as_list(buffers)
        single = isinstance(buffers, np.ndarray)
        # the ring divides (comm.h average_buffer): AVG is SUM over the world
        op, divisor = _sum_divisor(op, divisor, self._world_size)

        def _run() -> object:
            out: List[np.ndarray] = [None] * len(arrays)  # type: ignore[list-item]
            # one native call per dtype (each dtype needs its own reduce
            # loop); the arrays of a group ride ONE ring as scattered iovec
            # segments — the round-1 binding np.concatenate'd them into a
            # staging buffer and sliced the result back out, a full extra
            # payload copy each way
            by_dtype: Dict[str, List[int]] = {}
            for i, a in enumerate(arrays):
                by_dtype.setdefault(a.dtype.name, []).append(i)
            for group, (dtype_name, idxs) in enumerate(by_dtype.items()):
                code = _DTYPE_CODES.get(dtype_name)
                if code is None:
                    raise CommunicatorError(f"unsupported dtype {dtype_name}")
                flats: List[np.ndarray] = []
                for i in idxs:
                    a = arrays[i]
                    if (
                        in_place
                        and a.flags.c_contiguous
                        and a.flags.writeable
                    ):
                        # zero-copy: the native ring reduces straight into
                        # the caller's buffer (returned aliased)
                        flat = a.reshape(-1)
                    else:
                        # the native op is in-place; copy this one array to
                        # preserve the caller's buffer (also the landing
                        # spot for read-only dlpack views)
                        flat = np.array(a, copy=True).reshape(-1)
                    flats.append(flat)
                    out[i] = flat
                total = sum(int(f.nbytes) for f in flats)
                if total > 0:
                    ptrs = (ctypes.c_void_p * len(flats))(
                        *(_data_ptr(f) for f in flats)
                    )
                    lens = (ctypes.c_uint64 * len(flats))(
                        *(int(f.nbytes) for f in flats)
                    )
                    self._ring_calls += 1
                    self._check(
                        self._lib.tpuft_comm_allreduce_iov(
                            self._h, ptrs, lens, len(flats), code,
                            _OP_CODES[op], divisor or 0, group,
                        ),
                        "allreduce",
                    )
                for i in idxs:
                    out[i] = out[i].reshape(arrays[i].shape)
            return out[0] if single else out

        return self._submit(_run)

    def ring_session(self, pieces: int, divisor: Optional[int] = None) -> Optional[RingSession]:
        """Open a :class:`RingSession` of ``pieces`` SUM rings (``divisor``:
        each comes back as SUM / divisor, as :meth:`allreduce`'s) and put its
        ONE native call on the op thread, behind the ops submitted before.
        None where there is no ring to stay inside (one member) or the
        communicator takes no op now (errored, not configured): the caller's
        per-call path says why, as it always did.

        Inside a session a group's lanes are runnable from the round trip's
        first bucket to its last; the per-call path's way back into Python
        between two rings was also a pause in which everything else on the
        host caught up.  A host with cores to spare (two groups on 30) rings
        as fast without it and ends the round trip 5 % sooner; where the
        groups' threads crowd the host (both groups of a benchmark cell in
        ONE process on 13 cores, the loopback as their wire) the round trip
        alone read 6-16 % longer (PERF.md section 6, PR 60: the readings, what
        was tried against it, and why the cause is not known)."""
        if self._world_size <= 1 or pieces < 1:
            return None
        session = RingSession(self._lib, pieces, divisor or 0)

        def _run() -> object:
            self._ring_calls += 1
            self._check(self._lib.tpuft_ring_session_run(self._h, session._s), "ring session")
            return None

        _run.session = session  # type: ignore[attr-defined]
        work = self._submit(_run)
        if work.done() and work.exception() is not None:
            return None
        session.work = work

        def _ended(fut: Future) -> None:
            # a run that failed in C has told the session; one that never
            # began (the queue was drained by a teardown) tells it here
            err = fut.exception()
            if err is not None:
                session.fail(str(err))
            # C reads the buffers no more, and the session lets go of the
            # future that holds this callback (no cycle for the collector)
            session._kept.clear()
            session.work = DummyWork() if err is None else failed_work(err)

        work.future().add_done_callback(_ended)
        return session

    def reduce_scatter(
        self, data: np.ndarray, op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        arr = np.asarray(data)
        ws = self._world_size

        def _run() -> object:
            code = _DTYPE_CODES.get(arr.dtype.name)
            if code is None:
                raise CommunicatorError(f"unsupported dtype {arr.dtype.name}")
            # the native op reduces in place; work on a copy so the caller's
            # buffer survives
            flat = np.array(arr, copy=True).reshape(-1)
            n = flat.size
            base, extra = divmod(n, ws)
            own_elems = base + (1 if self._rank < extra else 0)
            out = np.empty(own_elems, dtype=flat.dtype)
            got = ctypes.c_uint64()
            self._check(
                self._lib.tpuft_comm_reduce_scatter(
                    self._h,
                    _data_ptr(flat),
                    flat.nbytes,
                    code,
                    _OP_CODES[op],
                    _data_ptr(out),
                    out.nbytes,
                    ctypes.byref(got),
                ),
                "reduce_scatter",
            )
            assert got.value == out.nbytes, "reduce_scatter size mismatch"
            if op == ReduceOp.AVG:
                if np.issubdtype(out.dtype, np.integer):
                    out //= ws
                else:
                    np.divide(out, ws, out=out)
            return out

        return self._submit(_run)

    def broadcast(self, buffers: Buffers, root: int = 0) -> Work:
        arrays = [np.ascontiguousarray(a) for a in self._as_list(buffers)]
        single = isinstance(buffers, np.ndarray)

        def _run() -> object:
            out = []
            for a in arrays:
                buf = np.array(a, copy=True)
                view = buf.reshape(-1).view(np.uint8)
                self._check(
                    self._lib.tpuft_comm_broadcast(
                        self._h,
                        view.ctypes.data_as(ctypes.c_void_p),
                        view.nbytes,
                        root,
                    ),
                    "broadcast",
                )
                out.append(buf)
            return out[0] if single else out

        return self._submit(_run)

    def send_bytes(self, data, dst: int, tag: int = 0) -> Work:
        """Send any contiguous buffer (bytes, memoryview, numpy array)
        WITHOUT copying: the C call reads straight from the object's buffer
        (the closure keeps it alive until the op completes)."""
        ptr, nbytes, keepalive = _buffer_ptr(data)

        def _run(_keep=keepalive) -> object:
            # _keep pins the backing buffer for the C call's lifetime
            self._check(
                self._lib.tpuft_comm_send(self._h, ptr, nbytes, dst, tag),
                "send",
            )
            return nbytes

        return self._submit(_run)

    def recv_bytes(self, src: int, tag: int = 0) -> Work:
        def _run() -> object:
            out = ctypes.POINTER(ctypes.c_uint8)()
            n = ctypes.c_uint64()
            self._check(
                self._lib.tpuft_comm_recv_alloc(
                    self._h, src, tag, ctypes.byref(out), ctypes.byref(n)
                ),
                "recv",
            )
            try:
                return ctypes.string_at(out, n.value)
            finally:
                self._lib.tpuft_buffer_free(out)

        return self._submit(_run)

    def recv_bytes_into(self, src: int, out: np.ndarray, tag: int = 0) -> Work:
        assert out.flags.c_contiguous and out.flags.writeable

        def _run() -> object:
            n = ctypes.c_uint64()
            self._check(
                self._lib.tpuft_comm_recv_into(
                    self._h,
                    src,
                    tag,
                    out.ctypes.data_as(ctypes.c_void_p),
                    out.nbytes,
                    ctypes.byref(n),
                ),
                "recv_into",
            )
            return int(n.value)

        return self._submit(_run)

    def alltoall(self, chunks: List[np.ndarray], tag: int = 0) -> Work:
        arrays = [
            np.ascontiguousarray(as_host_array(c)) for c in chunks
        ]

        def _run() -> object:
            ws = self._world_size
            if ws == 1:
                return [arrays[0]]
            assert len(arrays) == ws
            chunk_bytes = arrays[0].nbytes
            assert all(a.nbytes == chunk_bytes for a in arrays), (
                "cpp alltoall requires equal-size chunks"
            )
            # one pointer per destination chunk: frames leave straight from
            # the callers' buffers (the round-1 binding packed them into a
            # staging concatenation first); receives land in one buffer
            # handed back as per-source views
            ptrs = (ctypes.c_void_p * ws)(*(_data_ptr(a) for a in arrays))
            out = np.empty(ws * chunk_bytes, dtype=np.uint8)
            self._check(
                self._lib.tpuft_comm_alltoall_ptrs(
                    self._h,
                    ptrs,
                    out.ctypes.data_as(ctypes.c_void_p),
                    chunk_bytes,
                    tag,
                ),
                "alltoall",
            )
            return [
                out[p * chunk_bytes : (p + 1) * chunk_bytes]
                .view(arrays[0].dtype)
                .reshape(arrays[0].shape)
                for p in range(ws)
            ]

        return self._submit(_run)

    def allgather(self, data: np.ndarray, tag: int = 0) -> Work:
        array = np.ascontiguousarray(data)

        def _run() -> object:
            ws = self._world_size
            if ws == 1:
                return [array]
            chunk_bytes = array.nbytes
            out = np.empty(ws * chunk_bytes, dtype=np.uint8)
            self._check(
                self._lib.tpuft_comm_allgather(
                    self._h,
                    array.reshape(-1).view(np.uint8).ctypes.data_as(ctypes.c_void_p),
                    out.ctypes.data_as(ctypes.c_void_p),
                    chunk_bytes,
                    tag,
                ),
                "allgather",
            )
            return [
                out[p * chunk_bytes : (p + 1) * chunk_bytes]
                .view(array.dtype)
                .reshape(array.shape)
                for p in range(ws)
            ]

        return self._submit(_run)

    def barrier(self) -> Work:
        def _run() -> object:
            self._check(self._lib.tpuft_comm_barrier(self._h), "barrier")
            return None

        return self._submit(_run)
