"""ctypes loader for the native data-plane core (native/build/libtpurpc.so).

The reference's entire data plane is C++ (``src/core/lib/ibverbs/``); ours
keeps the state machines in Python and pushes the per-byte work — framed-ring
scan/copy/zero with proper acquire/release fences — into C++. Pure-Python
fallbacks stay in tpurpc/core/ring.py; ``TPURPC_NATIVE=0`` forces them (both
paths are covered by the same test suite).
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

_LIB: "Optional[ctypes.CDLL]" = None
_SPIN: "Optional[ctypes.CDLL]" = None
_TRIED = False
#: why load() returned None, for status(): the Python data plane must never
#: be what ran without anyone being able to say so
_WHY = "load() not called yet"

ABI_VERSION = 9


def _src_dir() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "native", "src")


def sources_digest() -> str:
    """sha256 over ``native/src`` (names and contents of every .cc/.h): what
    an artifact must have been built from to be the checkout's data plane.
    mtimes say nothing after a checkout or a copy; contents do."""
    import hashlib

    h = hashlib.sha256()
    src = _src_dir()
    for name in sorted(os.listdir(src)):
        if name.endswith((".cc", ".h")):
            h.update(name.encode() + b"\0")
            with open(os.path.join(src, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def build_from_sources(out_path: str) -> None:
    """Compile ``native/src/*.cc`` into ``out_path`` — the one g++ invocation
    the first-use build also makes. Raises: ``FileNotFoundError`` when there
    is no ``g++``, ``subprocess.CalledProcessError`` (stderr attached) when
    the compile fails."""
    import glob
    import shutil
    import subprocess

    gxx = shutil.which("g++")
    if gxx is None:
        raise FileNotFoundError("g++ not found on PATH")
    srcs = sorted(glob.glob(os.path.join(_src_dir(), "*.cc")))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    tmp = f"{out_path}.{os.getpid()}.tmp"
    # -lrt: shm_open/shm_unlink live in librt on older glibc (< 2.34);
    # without it the link "succeeds" but dlopen fails with an undefined
    # symbol. Harmless where libc already provides them.
    subprocess.run(
        [gxx, "-std=c++17", "-O3", "-DNDEBUG", "-shared", "-fPIC", *srcs,
         "-o", tmp, "-lpthread", "-lrt"],
        check=True, timeout=300, capture_output=True)
    os.replace(tmp, out_path)  # atomic: no partially-linked .so visible


def status() -> dict:
    """Which data plane this process runs, and why: ``{"plane": "native",
    "path": ...}`` or ``{"plane": "python", "why": ...}``."""
    if load() is not None:
        return {"plane": "native", "path": _lib_path()}
    return {"plane": "python", "why": _WHY}


def _lib_path() -> str:
    # TPURPC_NATIVE_LIB points the loader at an alternate artifact — e.g. a
    # TPURPC_SANITIZE=thread build (tools/check.sh) — without clobbering the
    # release .so. A sanitized lib additionally needs the sanitizer runtime
    # preloaded into the (uninstrumented) Python process:
    #   LD_PRELOAD=libtsan.so.0 TPURPC_NATIVE_LIB=… python -m pytest …
    override = os.environ.get("TPURPC_NATIVE_LIB")
    if override:
        return override
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "native", "build", "libtpurpc.so")


def _try_build(path: str) -> None:
    """Best-effort first-use build of the native core (fresh checkouts ship
    sources only). One direct g++ invocation — no cmake dependency — guarded
    by an exclusive lockfile so concurrent processes don't race the link;
    losers wait for the winner. Failure is fine: callers fall back to the
    pure-Python data plane. ``TPURPC_NATIVE_BUILD=0`` disables."""
    import glob
    import shutil

    if os.environ.get("TPURPC_NATIVE_BUILD", "1") == "0":
        return
    if os.environ.get("TPURPC_NATIVE_LIB"):
        return  # an explicitly pointed-at artifact is never auto-built
    if shutil.which("g++") is None:
        return
    build_dir = os.path.dirname(path)
    srcs = sorted(glob.glob(os.path.join(_src_dir(), "*.cc")))
    if not srcs:
        return
    os.makedirs(build_dir, exist_ok=True)
    lock_path = os.path.join(build_dir, ".build.lock")
    fail_stamp = os.path.join(build_dir, ".build.failed")
    try:
        import fcntl

        src_mtime = max(os.path.getmtime(s) for s in srcs)
        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # winner builds, losers wait here
            if os.path.exists(fail_stamp):
                # A prior attempt failed. Honor the stamp only while the
                # sources are unchanged — newer sources (a fix, a git pull)
                # invalidate it, as does a stamp older than the sources on
                # disk. A transient failure (loaded machine) is retried by
                # touching the sources or deleting native/build.
                try:
                    with open(fail_stamp) as f:
                        stamped = float(f.readline().strip() or 0)
                except (OSError, ValueError):
                    stamped = 0.0
                if stamped >= src_mtime:
                    return
                os.unlink(fail_stamp)
            if not os.path.exists(path):
                try:
                    build_from_sources(path)
                except Exception as exc:
                    # Stamp the failure so future processes skip the broken
                    # compile until the sources change.
                    with open(fail_stamp, "w") as f:
                        f.write(f"{src_mtime}\n{type(exc).__name__}: {exc}\n")
                    return
    except Exception:
        pass


def _open() -> "tuple[Optional[ctypes.CDLL], str]":
    """``(lib, "")`` or ``(None, why)``: find, if need be build, and dlopen
    the artifact, rebuilding a stale one from the sources once."""
    if os.environ.get("TPURPC_NATIVE", "1") == "0":
        return None, "TPURPC_NATIVE=0"
    path = _lib_path()
    explicit = bool(os.environ.get("TPURPC_NATIVE_LIB"))
    if not os.path.exists(path):
        _try_build(path)
    if not os.path.exists(path):
        return None, (f"{path} is absent and was not built (g++ missing, "
                      "build failed or disabled)")
    for attempt in (0, 1):
        try:
            # PyDLL: calls run WITH the GIL held. The ring ops take raw
            # pointers into shm segments whose lifetime is managed by Python
            # memoryview release + munmap on other threads; holding the GIL
            # makes each [liveness-check → native call] pair atomic against
            # teardown, the exact safety the pure-Python slicing path gets
            # implicitly.
            lib = ctypes.PyDLL(path)
            if lib.tpr_abi_version() == ABI_VERSION:
                return lib, ""
            why = (f"{path} has ABI {lib.tpr_abi_version()}, "
                   f"want {ABI_VERSION}")
        except OSError as exc:
            # observed: a build without -lrt leaves shm_open undefined on
            # older glibc and dlopen fails
            why = f"dlopen {path}: {exc}"
        # A stale or mis-linked artifact: rebuild from the sources on disk
        # once instead of dropping the whole native data plane to Python
        # for the life of the process. An explicitly pointed-at
        # TPURPC_NATIVE_LIB is never deleted or rebuilt.
        if attempt or explicit:
            return None, why
        try:
            os.unlink(path)
        except OSError:
            return None, why
        _try_build(path)
        if not os.path.exists(path):
            return None, why + "; rebuild failed"
    return None, why


def load() -> "Optional[ctypes.CDLL]":
    """The native library, or None (absent, disabled, or ABI-mismatched);
    :func:`status` says which and why."""
    global _LIB, _TRIED, _WHY
    if _TRIED:
        return _LIB
    _TRIED = True
    lib, _WHY = _open()
    if lib is None:
        return None
    u64 = ctypes.c_uint64
    pu64 = ctypes.POINTER(u64)
    pu8 = ctypes.c_void_p
    lib.tpr_ring_readable.restype = u64
    lib.tpr_ring_readable.argtypes = [pu8, u64, u64, u64, u64, u64]
    lib.tpr_ring_read_into.restype = u64
    lib.tpr_ring_read_into.argtypes = [pu8, u64, pu64, pu64, pu64, pu8, u64,
                                       pu64, pu64]
    lib.tpr_ring_writev.restype = u64
    lib.tpr_ring_writev.argtypes = [pu8, u64, pu64, u64,
                                    ctypes.POINTER(ctypes.c_void_p),
                                    pu64, ctypes.c_uint32, pu64]
    lib.tpr_ring_has_message.restype = ctypes.c_int
    lib.tpr_ring_has_message.argtypes = [pu8, u64, u64, u64, u64]
    # waiter-advertisement words (futex-style sleep handshake; see ring.cc)
    lib.tpr_store_u64_seqcst.restype = None
    lib.tpr_store_u64_seqcst.argtypes = [pu8, u64]
    lib.tpr_load_u64_fenced.restype = u64
    lib.tpr_load_u64_fenced.argtypes = [pu8]
    # fused hot-path send: credit fold + chunked gather-encode + notify
    # decision in one GIL-held call (see ring.cc tpr_send_fast)
    lib.tpr_send_fast.restype = u64
    lib.tpr_send_fast.argtypes = [pu8, u64, pu64, pu64, pu8, pu64, pu8,
                                  ctypes.POINTER(ctypes.c_void_p), pu64,
                                  ctypes.c_uint32, u64,
                                  ctypes.POINTER(ctypes.c_int)]
    _LIB = lib

    # Second handle via CDLL: these calls RELEASE the GIL — they are the
    # bounded busy-poll windows (BP/BPEV disciplines), and a spinning waiter
    # must not starve the very threads that produce what it waits for; and
    # the one payload-sized copy (tpr_place).
    # Callers pin the watched memory (an exported buffer view) across the
    # call; Region.close retries on BufferError until waiters unpin.
    spin = ctypes.CDLL(_lib_path())
    spin.tpr_ring_wait_message.restype = ctypes.c_int
    spin.tpr_ring_wait_message.argtypes = [pu8, u64, u64, u64, u64]
    spin.tpr_spin_u64_change.restype = ctypes.c_int
    spin.tpr_spin_u64_change.argtypes = [pu8, u64, u64]
    # the rendezvous sender's gather copy into the peer's landing region
    # (rendezvous.place_released): on THIS handle because a payload-sized
    # memcpy made holding the GIL holds every other thread of the process;
    # it returns the monotonic stamp of the copy's end (hop place_return)
    spin.tpr_place.restype = u64
    spin.tpr_place.argtypes = [pu8, pu64, ctypes.POINTER(ctypes.c_void_p),
                               pu64, ctypes.c_uint32]
    global _SPIN
    _SPIN = spin
    return _LIB


def load_spin() -> "Optional[ctypes.CDLL]":
    """GIL-releasing spin-wait entry points (None when native is unavailable)."""
    load()
    return _SPIN


def addr_of(buf, writable: bool) -> int:
    """Raw address of a buffer-protocol object without copying.

    numpy handles both read-only and writable exporters; the array is a view,
    so the caller must keep ``buf`` alive for the duration of the native call.
    """
    return pin(buf, writable)[1]


def pin(buf, writable: bool):
    """(array, address) for repeated native calls on a long-lived buffer.

    The returned array holds a buffer-protocol export: the underlying
    memoryview/shm segment cannot release while it is referenced, which is
    what makes a CACHED address safe to pass to native code. Owners must drop
    the pin before closing the buffer (close paths retry on BufferError for
    the in-flight-call window).

    ``__array_interface__`` instead of ``.ctypes.data``: the latter constructs
    a ctypes helper object per access, measurable on the per-RPC path."""
    arr = np.frombuffer(buf, dtype=np.uint8)
    if writable and not arr.flags.writeable:
        raise ValueError("writable buffer required")
    return arr, arr.__array_interface__["data"][0]


def reset_for_tests() -> None:
    global _LIB, _SPIN, _TRIED, _WHY
    _LIB = None
    _SPIN = None
    _TRIED = False
    _WHY = "load() not called yet"
