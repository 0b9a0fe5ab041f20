"""Build + load the native host library.

Compiled lazily with g++ into the package directory (falls back to a
temp dir when the package is read-only); the artifact name embeds a hash
of the source, so a stale or foreign binary is never loaded — only a
.so produced from the exact sentinel_host.cpp present on disk.  Binaries
are never committed to version control.  When no toolchain is available,
``load_native()`` returns None and callers use the pure-Python fallbacks.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

_SRC = os.path.join(os.path.dirname(__file__), "sentinel_host.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _src_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _so_path() -> str:
    name = f"_sentinel_host-{_src_digest()}.so"
    base = os.path.dirname(__file__)
    if os.access(base, os.W_OK):
        return os.path.join(base, name)
    # never a shared world-writable path: a pre-planted .so there would be
    # loaded into this process — use a per-user 0700 cache dir and refuse
    # anything not owned by us
    d = os.path.join(
        os.path.expanduser("~"), ".cache", "sentinel_tpu", "native"
    )
    os.makedirs(d, mode=0o700, exist_ok=True)
    st = os.stat(d)
    if st.st_uid != os.getuid() or (st.st_mode & 0o022):
        d = tempfile.mkdtemp(prefix="sentinel_tpu_native_")
    return os.path.join(d, name)


def _build(so: str) -> bool:
    # compile to a temp name, rename into place: a g++ killed mid-write
    # must never leave a truncated artifact at the final (hash-named,
    # existence-is-freshness) path
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        r = subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC],
            capture_output=True,
            text=True,
            timeout=120,
        )
        if r.returncode != 0:
            from sentinel_tpu.utils.record_log import record_log

            record_log().warning("native build failed: %s", r.stderr[-2000:])
            return False
        os.replace(tmp, so)
        # reap binaries from superseded source revisions (and the legacy
        # unhashed name from pre-hash checkouts)
        d = os.path.dirname(so)
        for name in os.listdir(d):
            stale = name == "_sentinel_host.so" or (
                name.startswith("_sentinel_host-")
                and name.endswith(".so")
                and os.path.join(d, name) != so
            )
            if stale:
                try:
                    os.unlink(os.path.join(d, name))
                except OSError:
                    pass
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    i32, i64, u32, u64, f32 = c.c_int32, c.c_int64, c.c_uint32, c.c_uint64, c.c_float
    p = c.c_void_p
    lib.sx_ring_new.restype = p
    lib.sx_ring_new.argtypes = [u64]
    lib.sx_ring_free.argtypes = [p]
    lib.sx_ring_push.restype = i32
    lib.sx_ring_push.argtypes = [
        p, i32, i32, i32, i32, i32, f32, i32, i32, i32, i32, i32, i32
    ]
    lib.sx_ring_drain.restype = i64
    lib.sx_ring_drain.argtypes = [p, i64] + [p] * 12
    lib.sx_ring_size.restype = i64
    lib.sx_ring_size.argtypes = [p]
    lib.sx_intern_new.restype = p
    lib.sx_intern_new.argtypes = [u64, i32, i32]
    lib.sx_intern_free.argtypes = [p]
    lib.sx_intern_get.restype = i32
    lib.sx_intern_get.argtypes = [p, c.c_char_p, u32]
    lib.sx_intern_count.restype = i32
    lib.sx_intern_count.argtypes = [p, i32]
    # native front door (epoll token-protocol server)
    lib.sx_front_new.restype = p
    lib.sx_front_new.argtypes = [i32, u64, u64, u64, i32]
    lib.sx_front_free.argtypes = [p]
    lib.sx_front_port.restype = i32
    lib.sx_front_port.argtypes = [p]
    lib.sx_front_start.restype = i32
    lib.sx_front_start.argtypes = [p]
    lib.sx_front_stop.argtypes = [p]
    lib.sx_front_map_flow.restype = i32
    lib.sx_front_map_flow.argtypes = [p, i64, i32]
    lib.sx_front_map_param.restype = i32
    lib.sx_front_map_param.argtypes = [p, i64, i32, i32]
    lib.sx_front_set_guard.argtypes = [p, i64]
    lib.sx_front_clear_flows.argtypes = [p]
    lib.sx_front_acq_backlog.restype = i64
    lib.sx_front_acq_backlog.argtypes = [p]
    lib.sx_front_drain_acquires.restype = i64
    lib.sx_front_drain_acquires.argtypes = [p, i64] + [p] * 4
    lib.sx_front_drain_acquires2.restype = i64
    lib.sx_front_drain_acquires2.argtypes = [p, i64] + [p] * 7
    lib.sx_front_respond.restype = i32
    lib.sx_front_respond.argtypes = [p, i64] + [p] * 3
    lib.sx_front_respond_ex.restype = i32
    lib.sx_front_respond_ex.argtypes = [p, i64] + [p] * 5
    # batch-build presort (stable multi-key argsort of the live rows, the
    # inverse permutation and every column's gather in one call)
    lib.sx_presort.restype = i32
    lib.sx_presort.argtypes = [i64, i64, i32, p, p, p, p, i32, p, p, i32, p, p]
    # protocol v2 BATCH framing (big-endian column entries <-> int columns)
    lib.sx_frame_pack_entries.restype = i64
    lib.sx_frame_pack_entries.argtypes = [i64] + [p] * 5
    lib.sx_frame_unpack_entries.restype = i64
    lib.sx_frame_unpack_entries.argtypes = [i64] + [p] * 5
    lib.sx_frame_pack_results.restype = i64
    lib.sx_frame_pack_results.argtypes = [i64] + [p] * 5
    lib.sx_frame_unpack_results.restype = i64
    lib.sx_frame_unpack_results.argtypes = [i64] + [p] * 5
    return lib


def load_native() -> Optional[ctypes.CDLL]:
    """The bound CDLL, building it on first use; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        so = _so_path()
        # the hash in the filename ties the binary to this exact source —
        # existence is sufficient freshness
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            _LIB = _bind(ctypes.CDLL(so))
        except OSError:
            _LIB = None
        return _LIB


def native_available() -> bool:
    return load_native() is not None
