"""Tensor wire codec: jax.Array / numpy ↔ framed bytes, zero-copy on decode.

This is the serialization half of the ``grpcio-jax`` shim called for by
BASELINE.json: the reference ships tensors as opaque protobuf ``bytes`` fields
(every byte is copied at least twice — protobuf serialize + ``grpc_slice``
assembly, reference ``src/core/lib/surface/byte_buffer.cc``); we define a raw
layout a receiver can alias in place:

    [4B magic 'TPT1'][1B dtype][1B ndim][2B reserved][8B payload nbytes]
    [ndim x 8B little-endian dims][row-major payload, 64B-aligned start]

The 64-byte alignment of the payload start lets the decoded view satisfy
dlpack/XLA alignment so ``decode → jax.Array`` needs no repack; the copy ledger
(:mod:`tpurpc.tpu.ledger`) records whether a given decode aliased or copied.

Pytrees are carried as a count-prefixed concatenation of tensor records plus a
JSON treedef trailer, so arbitrary ``(params, batch)`` structures ship in one
message.
"""

from __future__ import annotations

import json
import struct
from typing import Any, List, Optional, Tuple

import numpy as np

from tpurpc.obs import lens as _lens
from tpurpc.obs import profiler as _profiler

# tpurpc-lens (ISSUE 8) waterfall hops on the codec boundary: `device` is
# the serialize leg (header + gather list over tensor bytes that are on the
# host; a reply's device leaves were read back under `d2h` before),
# `decode` the parse back, `jax_array` the final materialization. One
# `lens.stage` per tensor record / tree record — never per byte.

_LENS_STAGES = {
    "encode_tensor": "codec",
    "encode_tree": "codec",
    "decode_tensor": "codec",
    "decode_tree_at": "codec",
    "decode_tree_many": "codec",
    "to_jax": "device-dispatch",
}
_profiler.register_stages(__file__, _LENS_STAGES)

try:  # bfloat16 et al. — baked into the image alongside jax
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
    _FP8_E4M3 = np.dtype(ml_dtypes.float8_e4m3fn)
    _FP8_E5M2 = np.dtype(ml_dtypes.float8_e5m2)
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    _BFLOAT16 = _FP8_E4M3 = _FP8_E5M2 = None

MAGIC = b"TPT1"
_ALIGN = 64

# dtype code table. Codes are wire ABI — append only, never renumber.
_DTYPES: List[Tuple[int, "np.dtype | None"]] = [
    (0, np.dtype(np.float32)),
    (1, np.dtype(np.float64)),
    (2, np.dtype(np.int8)),
    (3, np.dtype(np.int16)),
    (4, np.dtype(np.int32)),
    (5, np.dtype(np.int64)),
    (6, np.dtype(np.uint8)),
    (7, np.dtype(np.uint16)),
    (8, np.dtype(np.uint32)),
    (9, np.dtype(np.uint64)),
    (10, np.dtype(np.float16)),
    (11, _BFLOAT16),
    (12, np.dtype(np.bool_)),
    (13, np.dtype(np.complex64)),
    (14, np.dtype(np.complex128)),
    (15, _FP8_E4M3),
    (16, _FP8_E5M2),
]
_CODE_TO_DTYPE = {c: d for c, d in _DTYPES if d is not None}
_DTYPE_TO_CODE = {d: c for c, d in _DTYPES if d is not None}

_HDR = struct.Struct("<4sBBHQ")  # magic, dtype code, ndim, reserved, nbytes


class CodecError(ValueError):
    pass


def dtype_code(dt) -> int:
    dt = np.dtype(dt)
    try:
        return _DTYPE_TO_CODE[dt]
    except KeyError:
        raise CodecError(f"unsupported wire dtype {dt}") from None


def _as_numpy(x) -> np.ndarray:
    """Materialize x host-side without gratuitous copies.

    jax.Array → np.asarray uses the dlpack/buffer protocol: zero-copy when the
    array is already in host memory (CPU backend). An array on a TPU is read
    back here, blocking, only when a caller hands one to the codec directly
    (a client that sends a device array); a ``device=True`` reply's leaves
    arrive as the landing buffers of transfers
    :func:`tpurpc.tpu.serialize.tree_from_device` started and billed.
    """
    if isinstance(x, np.ndarray):
        return np.ascontiguousarray(x)
    return np.ascontiguousarray(np.asarray(x))


def encode_tensor(x) -> List[bytes]:
    """Encode one array as a gather list: [header+dims+pad, payload_view].

    Returns buffer segments rather than one joined blob so the endpoint layer
    can scatter-gather them into the ring without an intermediate copy
    (reference: ``PairPollable::Send`` builds one doorbell from a grpc_slice*
    gather list, ``ibverbs/pair.cc:645-734``).
    """
    with _lens.stage("device") as st:
        arr = _as_numpy(x)
        st.nbytes = arr.nbytes
        # contiguity copies are provable for ndarray inputs
        # (ascontiguousarray returns the same object when it aliased); a jax
        # input's d2h gather is the ledger's jurisdiction, not double-counted
        # here
        if isinstance(x, np.ndarray) and arr is not x:
            st.copy = arr.nbytes
        code = dtype_code(arr.dtype)
        dims = struct.pack(f"<{arr.ndim}q", *arr.shape) if arr.ndim else b""
        head = _HDR.pack(MAGIC, code, arr.ndim, 0, arr.nbytes) + dims
        pad = (-len(head)) % _ALIGN
        head += b"\x00" * pad
        payload = arr.reshape(-1).view(np.uint8).data  # memoryview, no copy
    return [head, payload]


def encode_tensor_descriptor(x) -> Tuple[bytes, memoryview]:
    """Descriptor-only encode for rendezvous'd tensors (tpurpc-express,
    ISSUE 9): returns ``(descriptor, payload_view)`` where the descriptor
    is the header+dims+pad bytes the framed control path carries, and the
    payload view ALIASES the array's memory (or its d2h landing buffer) —
    the bytes the one-sided rendezvous write places directly into the
    peer's landing region. :func:`decode_tensor_external` is the inverse,
    grafting the externally-landed payload back under the descriptor with
    zero copies."""
    head, payload = encode_tensor(x)
    return bytes(head), memoryview(payload).cast("B")


def decode_tensor_external(desc, payload) -> np.ndarray:
    """Rebuild a tensor from a descriptor (control path) and its
    externally-delivered payload (the rendezvous landing region). The
    returned array is a zero-copy view over ``payload`` — with a
    64B-aligned landing region (the pool guarantees it), ``to_jax``
    dlpack-aliases it onward with no movement."""
    view = memoryview(desc)
    if len(view) < _HDR.size:
        raise CodecError("short tensor descriptor")
    magic, code, ndim, _, nbytes = _HDR.unpack_from(view, 0)
    if magic != MAGIC:
        raise CodecError(f"bad tensor magic {magic!r}")
    try:
        dt = _CODE_TO_DTYPE[code]
    except KeyError:
        raise CodecError(f"unknown dtype code {code}") from None
    if len(view) < _HDR.size + 8 * ndim:
        raise CodecError("short tensor descriptor dims")
    shape = struct.unpack_from(f"<{ndim}q", view, _HDR.size) if ndim else ()
    pv = memoryview(payload).cast("B")
    if len(pv) < nbytes:
        raise CodecError(f"external payload short: want {nbytes}, "
                         f"have {len(pv)}")
    expect = (int(np.prod(shape, dtype=np.int64)) * dt.itemsize
              if ndim else dt.itemsize)
    if expect != nbytes:
        raise CodecError(f"shape/nbytes mismatch: {shape} x {dt} "
                         f"!= {nbytes}")
    flat = np.frombuffer(pv, dtype=np.uint8, count=nbytes)
    return flat.view(dt).reshape(shape)


def encode_tensor_bytes(x) -> bytes:
    # materializing convenience API (tests/interop): accumulate, don't join
    out = bytearray()
    for s in encode_tensor(x):
        out += s
    return bytes(out)


def decode_tensor(buf, offset: int = 0, copy: bool = False) -> Tuple[np.ndarray, int]:
    """Decode one tensor record from ``buf`` at ``offset``.

    Returns ``(array, next_offset)``. With ``copy=False`` the array is a
    zero-copy view aliasing ``buf`` (the ledger's "host-memcpy bytes = 0"
    receive path); the caller owns keeping ``buf`` alive.
    """
    view = memoryview(buf)
    if len(view) - offset < _HDR.size:
        raise CodecError("short tensor header")
    magic, code, ndim, _, nbytes = _HDR.unpack_from(view, offset)
    if magic != MAGIC:
        raise CodecError(f"bad tensor magic {magic!r}")
    try:
        dt = _CODE_TO_DTYPE[code]
    except KeyError:
        raise CodecError(f"unknown dtype code {code}") from None
    pos = offset + _HDR.size
    if len(view) - pos < 8 * ndim:
        raise CodecError("short tensor dims")
    shape = struct.unpack_from(f"<{ndim}q", view, pos) if ndim else ()
    pos += 8 * ndim
    pos += (-(pos - offset)) % _ALIGN
    if len(view) - pos < nbytes:
        raise CodecError(f"short tensor payload: want {nbytes}, have {len(view) - pos}")
    expect = int(np.prod(shape, dtype=np.int64)) * dt.itemsize if ndim else dt.itemsize
    if expect != nbytes:
        raise CodecError(f"shape/nbytes mismatch: {shape} x {dt} != {nbytes}")
    flat = np.frombuffer(view, dtype=np.uint8, count=nbytes, offset=pos)
    arr = flat.view(dt).reshape(shape)
    if copy:
        arr = arr.copy()
    return arr, pos + nbytes


def to_jax(arr: np.ndarray):
    """Host view → jax.Array on JAX's default device (``jax.default_device``
    respected), always.

    When that device is a CPU device, a writable view of a dtype dlpack can
    carry is imported in place: XLA adopts a 64-byte-aligned buffer without
    moving it (ledger ``zero_copy``, proven by pointer, never assumed).
    Everything else — an accelerator, a read-only view, bfloat16/fp8, a
    strided view — is ONE ``device_put``, billed ``dma_h2d``: on a TPU the one
    host→HBM DMA of the receive path.
    """
    import jax

    from tpurpc.tpu import ledger
    from tpurpc.utils.jaxenv import default_device

    nbytes = arr.nbytes
    with _lens.stage("jax_array", nbytes) as st:
        dev = default_device()
        aliased = False
        # what numpy's __dlpack__ exports and jax's import accepts: anything
        # else would raise, and an exception must not be what picks the
        # device
        if (dev.platform == "cpu" and arr.flags.writeable
                and arr.flags.c_contiguous and arr.dtype.kind in "biufc"):
            out = jax.dlpack.from_dlpack(arr, device=dev)
            aliased = (out.unsafe_buffer_pointer()
                       == arr.__array_interface__["data"][0])
        else:
            out = jax.device_put(arr, dev)
        if aliased:
            ledger.zero_copy(nbytes)
        else:
            ledger.dma_h2d(nbytes)
            st.copy = nbytes
    return out


# ---------------------------------------------------------------------------
# Pytrees: N tensor records + JSON treedef trailer
# ---------------------------------------------------------------------------

_TREE = struct.Struct("<4sIQ")  # magic 'TPTR', n_leaves, trailer nbytes
TREE_MAGIC = b"TPTR"


def flatten_tree(tree: Any) -> Tuple[Any, list]:
    """``(skeleton, leaves)`` of ``tree``: the JSON treedef the trailer
    carries and the leaves in jax's flatten order. Walked once per message."""
    leaves: list = []
    skeleton = _plain_flatten(tree, leaves)
    if skeleton is None:  # a container only jax's pytree registry can judge
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        skeleton = _treedef_to_json(treedef)
    return skeleton, leaves


def encode_flat(skeleton: Any, leaves: list) -> List[bytes]:
    """Gather list of a flattened tree (:func:`flatten_tree`): the leaves
    are host arrays, or arrays whose host copy is already at hand (the
    device leaves of a reply reach here through
    :func:`tpurpc.tpu.serialize.tree_from_device`)."""
    trailer = json.dumps(skeleton).encode()
    segs: List[bytes] = [_TREE.pack(TREE_MAGIC, len(leaves), len(trailer))]
    pad = (-_TREE.size) % _ALIGN
    if pad:
        segs.append(b"\x00" * pad)
    for leaf in leaves:
        segs.extend(encode_tensor(leaf))
        tail = segs[-1]
        rem = (-len(tail)) % _ALIGN
        if rem:
            segs.append(b"\x00" * rem)
    segs.append(trailer)
    return segs


def encode_tree(tree: Any) -> List[bytes]:
    """Encode an arbitrary pytree of arrays as a gather list."""
    return encode_flat(*flatten_tree(tree))


def encode_tree_bytes(tree: Any) -> bytes:
    # materializing convenience API (tests/interop): accumulate, don't join
    out = bytearray()
    for s in encode_tree(tree):
        out += s
    return bytes(out)


def decode_tree(buf, copy: bool = False, as_jax: bool = False,
                offset: int = 0) -> Any:
    tree, _ = decode_tree_at(buf, offset, copy=copy, as_jax=as_jax)
    return tree


def decode_tree_at(buf, offset: int = 0, copy: bool = False,
                   as_jax: bool = False) -> Tuple[Any, int]:
    """Decode one tree record at ``offset``; returns ``(tree, next_offset)``.

    All alignment arithmetic is RELATIVE to the record start, so records
    decode identically at any position — the walk primitive behind
    :func:`decode_tree_many`'s batched fast path over a contiguous drained
    buffer (memoryview offsets all the way down, no intermediate ``bytes``
    slices of the payload).
    """
    # tpurpc-lens `decode` hop: one stage per tree record (to_jax's share is
    # also visible on its own jax_array row — hops may nest)
    with _lens.stage("decode") as st:
        view = memoryview(buf)
        if len(view) - offset < _TREE.size:
            raise CodecError("short tree header")
        magic, n, trailer_len = _TREE.unpack_from(view, offset)
        if magic != TREE_MAGIC:
            raise CodecError(f"bad tree magic {magic!r}")
        pos = offset + _TREE.size + ((-_TREE.size) % _ALIGN)
        leaves = []
        payload = 0
        for _ in range(n):
            arr, pos = decode_tensor(view, pos, copy=copy)
            pos += (-(pos - offset)) % _ALIGN
            payload += arr.nbytes
            leaves.append(to_jax(arr) if as_jax else arr)
        # Trailer sits at the decode cursor — never measure from the buffer
        # end; zero-copy receive windows may carry ring-alignment slack
        # behind it.
        if len(view) - pos < trailer_len:
            raise CodecError("short tree trailer")
        trailer = view[pos:pos + trailer_len].tobytes()
        out = (unflatten(json.loads(trailer.decode()), leaves),
               pos + trailer_len)
        st.nbytes = payload
        if copy:
            st.copy = payload
    return out


def decode_tree_many(buf, count: Optional[int] = None, copy: bool = False,
                     as_jax: bool = False) -> List[Any]:
    """Batched decode: walk a contiguous buffer of back-to-back tree records
    (e.g. one ring drain's worth of messages) and return every tree.

    With ``count=None`` the walk stops cleanly at the buffer end or at the
    first position that does not start a record (zero-copy receive windows
    may carry ring-alignment slack behind the last record); a ``count``
    makes truncation an error instead. The buffer is sliced by memoryview
    offsets only — one decode pass, no per-record ``bytes`` copies.
    """
    view = memoryview(buf)
    out: List[Any] = []
    pos = 0
    while count is None or len(out) < count:
        if len(view) - pos < _TREE.size:
            if count is not None:
                raise CodecError(
                    f"short batch: {len(out)} of {count} tree records")
            break
        if view[pos:pos + 4].tobytes() != TREE_MAGIC:  # 4-byte peek
            if count is not None:
                raise CodecError(f"bad tree magic at batch offset {pos}")
            break
        tree, pos = decode_tree_at(view, pos, copy=copy, as_jax=as_jax)
        out.append(tree)
    return out


class _LeafSentinel:
    """Marks leaf positions in the treedef skeleton; distinct from a literal
    ``None`` node so trees carrying optional/None entries round-trip."""


_SENTINEL = _LeafSentinel()


def _treedef_to_json(treedef) -> Any:
    import jax

    skeleton = jax.tree_util.tree_unflatten(
        treedef, [_SENTINEL] * treedef.num_leaves)
    return _skel_to_json(skeleton)


_LEAF = {"__leaf__": 1}
_NONE = {"__none__": 1}


def _key_to_json(k) -> Any:
    if isinstance(k, str):
        return {"t": "s", "v": k}
    if isinstance(k, bool):  # before int: bool is an int subclass
        return {"t": "b", "v": k}
    if isinstance(k, int):
        return {"t": "i", "v": k}
    raise CodecError(f"unsupported dict key {k!r} (str/int/bool only)")


def _key_from_json(j) -> Any:
    return {"s": str, "b": bool, "i": int}[j["t"]](j["v"])


def _skel_to_json(s) -> Any:
    if s is _SENTINEL:
        return _LEAF
    if s is None:
        return _NONE
    if isinstance(s, (list, tuple)):
        return {"__seq__": "list" if isinstance(s, list) else "tuple",
                "items": [_skel_to_json(v) for v in s]}
    if isinstance(s, dict):
        return {"__dict__": [[_key_to_json(k), _skel_to_json(v)]
                             for k, v in s.items()]}
    raise CodecError(f"unsupported pytree node {type(s)!r}")


def _plain_flatten(tree, leaves: list) -> Any:
    """JSON skeleton of a tree built from exactly dict/list/tuple/None, with
    its leaves appended to ``leaves`` in jax's flatten order (dict keys
    sorted) — computed without importing jax, so a process that only ships
    numpy tensors never loads it. Returns None for a tree holding anything
    else (namedtuple, OrderedDict, a registered node class): only jax's
    registry knows how those flatten, and the caller asks it."""
    if tree is None:
        return _NONE
    t = type(tree)
    if t is list or t is tuple:
        items = []
        for v in tree:
            j = _plain_flatten(v, leaves)
            if j is None:
                return None
            items.append(j)
        return {"__seq__": "list" if t is list else "tuple", "items": items}
    if t is dict:
        try:
            keys = sorted(tree)
        except TypeError:
            return None
        pairs = []
        for k in keys:
            j = _plain_flatten(tree[k], leaves)
            if j is None:
                return None
            pairs.append([_key_to_json(k), j])
        return {"__dict__": pairs}
    if (isinstance(tree, (np.ndarray, np.generic, bool, int, float, complex))
            or hasattr(tree, "__array__")):
        leaves.append(tree)
        return _LEAF
    return None


def unflatten(skeleton, leaves: list) -> Any:
    """Rebuild the tree a decoded JSON ``skeleton`` describes around
    ``leaves`` (flatten order). The wire format only carries
    dict/list/tuple/None nodes, so this needs no jax; dict entries are
    visited in sorted-key order whatever order the sender wrote them in,
    which is the order jax flattens — and a jax-side sender encodes — in."""
    it = iter(leaves)
    try:
        tree = _fill(skeleton, it)
    except StopIteration:
        raise CodecError("treedef has more leaves than the message") from None
    for _ in it:
        raise CodecError("message has more leaves than its treedef")
    return tree


def _fill(j, it) -> Any:
    if j == _LEAF:
        return next(it)
    if j == _NONE:
        return None
    if isinstance(j, dict) and "__seq__" in j:
        items = [_fill(v, it) for v in j["items"]]
        return items if j["__seq__"] == "list" else tuple(items)
    if isinstance(j, dict) and "__dict__" in j:
        pairs = [(_key_from_json(k), v) for k, v in j["__dict__"]]
        try:
            pairs.sort(key=lambda kv: kv[0])
        except TypeError:
            pass  # unsortable mixed keys: the sender's order stands
        return {k: _fill(v, it) for k, v in pairs}
    raise CodecError(f"bad treedef json {j!r}")


# Serializer/Deserializer adapters for the rpc layer.
# Serializers return GATHER LISTS — the frame writer scatter-writes the
# segments (ring slice-gather / sendmsg) so the tensor payload is never
# joined into an intermediate host buffer.

def tensor_serializer(x) -> List[bytes]:
    return encode_tensor(x)


def tensor_deserializer(buf) -> np.ndarray:
    with _lens.stage("decode") as st:
        arr, _ = decode_tensor(buf)
        st.nbytes = arr.nbytes
    return arr


def tree_serializer(tree) -> List[bytes]:
    return encode_tree(tree)


def tree_deserializer(buf) -> Any:
    return decode_tree(buf)


def raw_view(buf):
    """Identity deserializer that opts INTO receiving the assembly view
    (``alias_ok``): device-mode tensor handlers decode it themselves."""
    return buf


# These decode zero-copy over the received assembly view; the rpc layer hands
# them the memoryview as-is instead of materializing grpcio-style bytes
# (tpurpc.rpc.status.deserialize).
tensor_deserializer.alias_ok = True
tree_deserializer.alias_ok = True
raw_view.alias_ok = True
