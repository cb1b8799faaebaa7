"""tpurpc-xray: Python face of the native observability plane (ISSUE 19).

The C core (``native/src/tpr_obs.cc``) keeps a flight ring and a fixed-slot
metrics table in ONE shm region; this module attaches to that region and
decodes it — the read path is an mmap + struct walk, zero ctypes calls per
record. Three consumers sit on top:

* :func:`records` / :func:`tag_table` — raw flight tuples for
  :mod:`tpurpc.obs.flight`'s merged snapshot (lane ``"native"``);
* :func:`counters` — the metrics table as a name → value dict (names
  mirror ``MetricIdx`` in tpr_obs.h IN ORDER — the index is the ABI);
* :func:`sync_registry` — pushes the table into the PR 4 registry as
  ``native_*`` series and feeds the lens waterfall's native hops. It is
  one of the registry's collectors (registered at this module's import,
  which ``tpurpc.obs`` does): every export of the registry (``snapshot``,
  ``counters_snapshot``, /metrics, tsdb ticks, /debug/waterfall) runs it
  first, so ``native_*`` is as of the same instant as everything else.

The decoder honors the writer's seqlock: per slot it reads the seq word,
copies the record, and re-reads the seq word — a wrap during the copy
changes the stamp and the slot is skipped (torn reads are detected, never
returned). Record order across slots comes from the stamps; the merged
flight view sorts on the shared CLOCK_MONOTONIC timeline.

``TPURPC_NATIVE_OBS=0`` (read by the C side at first use) leaves the
plane off: every entry point here degrades to empty/no-op and the PR 18
``tpr_rdv_counters`` ledger ABI is untouched either way.
"""

from __future__ import annotations

import atexit
import mmap
import os
import struct
import sys
import threading
from typing import Dict, List, Optional, Tuple

__all__ = [
    "METRIC_NAMES", "GAUGE_METRICS", "available", "records", "tag_table",
    "counters", "sync_registry", "reset", "postfork_reset",
]

LAYOUT_VERSION = 1
RECORD_BYTES = 32
_MAGIC = 0x54505258  # 'TPRX'

#: the metrics-table ABI: index here == MetricIdx in native/src/tpr_obs.h.
#: Append-only, like the event codes.
METRIC_NAMES: Tuple[str, ...] = (
    "rdv_send_bytes",      # one-sided bytes placed by rdv_write
    "rdv_send_busy_ns",    # ns inside the placement memcpy
    "rdv_recv_bytes",      # region bytes delivered to the stream layer
    "rdv_recv_busy_ns",    # ns inside deliver()
    "rdv_wait_ns",         # ns senders spent waiting on solicited claims
    "rdv_waits",           # solicited claim waits begun
    "rdv_fallbacks",       # eligible sends that fell back framed
    "ctrl_drain_batches",  # non-empty ctrl_drain passes
    "ctrl_drain_records",  # records drained across those passes
    "ctrl_kicks",          # framed kicks sent to a parked consumer
    "ctrl_posts",          # records placed in the peer's ring
    "ctrl_frames",         # control ops that went framed (ring miss/cold)
    "pin_waits",           # close() paths that found window pins held
    "pin_wait_ns",         # ns close() spent waiting for pins to drain
    "dlv_enqueued",        # delivery-shard items enqueued
    "dlv_drained",         # delivery-shard items delivered
    "dlv_stalls",          # backlog high-water crossings
    "dlv_depth",           # gauge: current delivery backlog
    "conn_up",             # connections established (native plane)
    "conn_down",           # connections died
    "emitted",             # flight records emitted (wraps overwrite)
    "tag_overflow",        # tag interns refused (table full -> tag 0)
    "srv_queue_ns",        # ns delivered messages sat on call->pending
    "srv_queue_msgs",      # messages popped off call->pending by a handler
    "rdv_refused",         # offers the receiver refused (landing pool empty)
)

#: table slots that are instantaneous values, not monotonic totals
GAUGE_METRICS = frozenset({"dlv_depth"})

_REC = struct.Struct("<QHHIqq")
_U64 = struct.Struct("<Q")
_U32 = struct.Struct("<I")

_lock = threading.Lock()
#: the handle whose tpr_obs_* signatures are declared: a reload of the
#: library (_native.reset_for_tests) hands out a new one that has none
_bound: Optional[object] = None


class _Map:
    """One attached shm region: mmap + parsed header offsets."""

    __slots__ = ("name", "mm", "capacity", "tag_cap", "metrics_cap",
                 "metrics_off", "tags_off", "seq_off", "rec_off")

    def __init__(self, name: str, mm: mmap.mmap):
        self.name = name
        self.mm = mm
        (magic,) = _U32.unpack_from(mm, 0)
        (version,) = _U32.unpack_from(mm, 4)
        if magic != _MAGIC or version != LAYOUT_VERSION:
            raise ValueError(f"tpr_obs layout mismatch "
                             f"(magic={magic:#x} version={version})")
        (self.capacity,) = _U32.unpack_from(mm, 8)
        (self.tag_cap,) = _U32.unpack_from(mm, 12)
        (self.metrics_cap,) = _U32.unpack_from(mm, 16)
        (rb,) = _U32.unpack_from(mm, 20)
        if rb != RECORD_BYTES:
            raise ValueError(f"tpr_obs record size mismatch ({rb})")
        (self.metrics_off,) = _U32.unpack_from(mm, 32)
        (self.tags_off,) = _U32.unpack_from(mm, 36)
        (self.seq_off,) = _U32.unpack_from(mm, 40)
        (self.rec_off,) = _U32.unpack_from(mm, 44)

    def tag_count(self) -> int:
        (n,) = _U32.unpack_from(self.mm, 48)
        return min(n, self.tag_cap)

    def close(self) -> None:
        try:
            self.mm.close()
        except Exception:
            pass


#: None = not tried, False = unavailable this process, _Map = attached
_state: Optional[object] = None


def _lib():
    from tpurpc.core import _native

    lib = _native.load()
    if lib is None or not hasattr(lib, "tpr_obs_enabled"):
        return None
    global _bound
    if _bound is not lib:
        import ctypes

        lib.tpr_obs_enabled.restype = ctypes.c_int
        lib.tpr_obs_enabled.argtypes = []
        lib.tpr_obs_shm_name.restype = ctypes.c_char_p
        lib.tpr_obs_shm_name.argtypes = []
        lib.tpr_obs_layout_version.restype = ctypes.c_uint32
        lib.tpr_obs_reset.restype = None
        lib.tpr_obs_reset.argtypes = []
        lib.tpr_obs_postfork.restype = None
        lib.tpr_obs_postfork.argtypes = []
        _bound = lib
    return lib


def _attach_locked():
    """(Re)attach to the C side's current region. Called under _lock."""
    global _state
    lib = _lib()
    if lib is None or not lib.tpr_obs_enabled():
        _state = False
        return None
    raw = lib.tpr_obs_shm_name()
    name = raw.decode("ascii", "replace") if raw else ""
    if not name:
        _state = False
        return None
    if isinstance(_state, _Map):
        if _state.name == name:
            return _state
        _state.close()  # the C side rebuilt (postfork): remap
        _state = None
    try:
        fd = os.open("/dev/shm/" + name, os.O_RDONLY)
        try:
            size = os.fstat(fd).st_size
            mm = mmap.mmap(fd, size, prot=mmap.PROT_READ)
        finally:
            os.close(fd)
        _state = _Map(name, mm)
    except (OSError, ValueError):
        _state = False
        return None
    return _state


def _map() -> Optional[_Map]:
    st = _state
    if isinstance(st, _Map):
        # cheap staleness probe: the C side swaps regions only on postfork,
        # which also swaps the advertised name
        lib = _lib()
        if lib is not None:
            raw = lib.tpr_obs_shm_name()
            if raw and raw.decode("ascii", "replace") == st.name:
                return st
        with _lock:
            return _attach_locked()
    if st is False:
        return None
    with _lock:
        if _state is None:
            return _attach_locked()
        return _state if isinstance(_state, _Map) else None


def available() -> bool:
    """True when the C plane is on and its region is mapped here."""
    return _map() is not None


def records() -> List[Tuple[int, int, int, int, int, int]]:
    """Seqlock-consistent snapshot of the flight ring as raw
    ``(t_ns, code, tag, tid, a1, a2)`` tuples (slot order — callers sort
    on ``t_ns``). Torn and empty slots are skipped."""
    st = _map()
    if st is None:
        return []
    mm = st.mm
    out: List[Tuple[int, int, int, int, int, int]] = []
    seq_off, rec_off = st.seq_off, st.rec_off
    for slot in range(st.capacity):
        so = seq_off + slot * 8
        (s1,) = _U64.unpack_from(mm, so)
        if s1 == 0:
            continue
        rec = bytes(mm[rec_off + slot * RECORD_BYTES:
                       rec_off + (slot + 1) * RECORD_BYTES])
        (s2,) = _U64.unpack_from(mm, so)
        if s2 != s1:
            continue  # a writer wrapped onto this slot mid-copy
        out.append(_REC.unpack(rec))
    return out


def tag_table() -> List[str]:
    """Interned entity names, indexed by native tag (0 = anonymous)."""
    st = _map()
    if st is None:
        return ["-"]
    out = ["-"]
    mm, base = st.mm, st.tags_off
    for i in range(st.tag_count()):
        off = base + i * 48
        (ln,) = struct.unpack_from("<H", mm, off)
        ln = min(ln, 46)
        out.append(bytes(mm[off + 2:off + 2 + ln]).decode("utf-8", "replace"))
    return out


def counters() -> Dict[str, int]:
    """The metrics table as ``{name: value}`` (empty when the plane is
    off). One relaxed-read pass over the shm slots."""
    st = _map()
    if st is None:
        return {}
    mm, base = st.mm, st.metrics_off
    n = min(len(METRIC_NAMES), st.metrics_cap)
    return {METRIC_NAMES[i]: _U64.unpack_from(mm, base + i * 8)[0]
            for i in range(n)}


# -- registry / lens sync -----------------------------------------------------

# the lens hop triples, bound ONCE at import with literal hop names (the
# `stage` lint rule's cached-counter contract); the table keys each hop
# mirrors ride alongside
from tpurpc.obs import lens as _lens  # noqa: E402  (after the ABI tables)
from tpurpc.obs import metrics as _metrics  # noqa: E402

_HOP_SYNC: Tuple[Tuple[Tuple, str, str], ...] = (
    (_lens.hop_counters("native_send"), "rdv_send_bytes",
     "rdv_send_busy_ns"),
    (_lens.hop_counters("native_recv"), "rdv_recv_bytes",
     "rdv_recv_busy_ns"),
    (_lens.hop_counters("native_rdv"), "rdv_send_bytes", "rdv_wait_ns"),
)


def _library_loaded() -> bool:
    """Whether either loader (the ring ops' ``core/_native``, the native
    client and server's ``rpc/native_client``) has libtpurpc open in this
    process. Looks; never imports, loads or builds."""
    return any(getattr(sys.modules.get(mod), "_LIB", None) is not None
               for mod in ("tpurpc.core._native", "tpurpc.rpc.native_client"))


def sync_registry() -> bool:
    """Mirror the native table into the PR 4 registry (``native_<name>``
    series: counters get their externally-owned running total, gauges the
    instantaneous value) and feed the lens waterfall's native hops.
    Export-time only — the registry runs this as a collector; the C hot
    path never sees Python. Returns False when off, and in a process that
    has not loaded the native library (an export must not be what builds
    or loads it)."""
    if not _library_loaded():
        return False
    vals = counters()
    if not vals:
        return False
    reg = _metrics.registry()
    for name, v in vals.items():
        if name in GAUGE_METRICS:
            reg.gauge("native_" + name).set(v)
        else:
            # value assignment, not inc(): the shm slot owns the total
            reg.counter("native_" + name).value = v
    for (b, ns, _cp), bkey, nkey in _HOP_SYNC:
        b.value = vals[bkey]
        ns.value = vals[nkey]
    return True


_metrics.registry().add_collector(sync_registry)


# -- test / lifecycle hooks ---------------------------------------------------

@atexit.register
def _close_map() -> None:
    """Unmap the region at interpreter exit: the C side unlinks its name
    there (tpr_obs.cc), and a mapping left open would keep the pages."""
    global _state
    with _lock:
        if isinstance(_state, _Map):
            _state.close()
        _state = False


def reset() -> None:
    """Zero the ring + table (test isolation; callers quiesce emitters
    first, the same promise flight.FlightRecorder.reset makes)."""
    lib = _lib()
    if lib is not None:
        lib.tpr_obs_reset()


def postfork_reset() -> None:
    """Forked shard worker: tell the C side to drop the inherited mapping
    (without unlinking the parent's region) and build its own, then drop
    our cached map so the next read attaches to the child's region."""
    global _state
    lib = _lib()
    if lib is not None:
        lib.tpr_obs_postfork()
    with _lock:
        if isinstance(_state, _Map):
            _state.close()
        _state = None


def reset_for_tests() -> None:
    """Forget the cached mapping/decision (mirrors _native.reset_for_tests
    — tests that flip TPURPC_NATIVE_OBS in-process re-probe)."""
    global _state, _bound
    with _lock:
        if isinstance(_state, _Map):
            _state.close()
        _state = None
        _bound = None
