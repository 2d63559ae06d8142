"""Binary wire protocol for the evaluation hot path — stdlib + numpy only.

The paper's deployed-kernel economics die on a JSON wire: a warm mapped
launch costs ~19µs behind the compile cache, but ``tolist()``-ing a
10⁵–10⁶-point coordinate block and re-parsing it client-side costs
milliseconds and tens of MB of text.  This module frames numpy arrays as
raw little-endian bytes with a small JSON metadata header, so the server
writes each array once into the frame's buffer and the client rehydrates
with ``np.frombuffer`` — zero text, zero per-element work, exact dtypes.

Frame layout (one response, or one streamed sweep cell)::

    offset 0   MAGIC            4 bytes  b"RPWF"
    offset 4   version          u32 LE   (currently 1)
    offset 8   header length    u32 LE
    offset 12  header           JSON, utf-8
    then, per segment:
               payload length   u32 LE
               payload          raw little-endian array bytes

The header is ``{"payload": <JSON structure>, "segments": [{"dtype":
"int32", "shape": [8, 4096]}, ...]}`` where every array in the original
payload is replaced by ``{"__nd__": i}`` — an index into ``segments``.
Decoding walks the structure back, attaching ``np.frombuffer`` views onto
the frame buffer.  Anything JSON-serializable passes through unchanged, so
the same codec frames a single result, a ``{"results": [...]}`` batch, and
each cell of a sweep stream.

Streams are length-prefixed: each cell is ``u32 LE frame length`` + frame,
and the stream end is connection close (the same close-delimited framing
the NDJSON sweeps use, so pull-driven backpressure carries over).

Negotiation: a client asks for binary with ``Accept:
application/x-repro-binary`` (or ``?format=binary``); servers that predate
this module ignore both and answer JSON, which clients detect from the
response Content-Type — fallback needs no version handshake.

Malformed frames (bad magic, truncated header or segment, unknown
version) raise :class:`WireFormatError`, a ``ValueError`` subclass so the
frontends' shared ``map_error`` turns it into a structured 400 — never a
500, never a hung keep-alive connection.
"""
from __future__ import annotations

import json
import struct
import threading
from collections import OrderedDict
from typing import Callable

import numpy as np

MAGIC = b"RPWF"
VERSION = 1

#: one binary frame (single result or {"results": [...]} batch)
CONTENT_TYPE = "application/x-repro-binary"
#: length-prefixed frame stream (the sweep surface); close-delimited
STREAM_CONTENT_TYPE = "application/x-repro-binary-stream"

_U32 = struct.Struct("<I")
_MAX_HEADER_BYTES = 1 << 20      # a metadata header past 1 MiB is corrupt
_MAX_SEGMENT_BYTES = 1 << 31     # and so is a >2 GiB single segment


class WireFormatError(ValueError):
    """A frame that cannot be decoded: wrong magic, unknown version,
    truncated header/segment, or a header that is not valid metadata.
    Subclasses ``ValueError`` so ``serving.http.map_error`` answers a
    structured 400 for wire-supplied garbage instead of a 500."""


# -- encode ------------------------------------------------------------------

def _strip_arrays(obj, segments: list[np.ndarray]):
    """Replace every ndarray in a JSON-ish structure with an ``{"__nd__":
    i}`` placeholder, collecting the arrays in order."""
    if isinstance(obj, np.ndarray):
        segments.append(obj)
        return {"__nd__": len(segments) - 1}
    if isinstance(obj, dict):
        return {k: _strip_arrays(v, segments) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_strip_arrays(v, segments) for v in obj]
    if isinstance(obj, np.generic):  # numpy scalar leaked into metadata
        return obj.item()
    return obj


#: a transposed view whose dim is at most this many columns is copied
#: column by column; wider ones (and every other layout) by ``np.copyto``.
#: On a TPU v5e host, into a frame already zero-filled, the column copy of
#: 2^21 int32 points took 2.7, 4.3, 6.8, 9.4 and 13.2 ms at 2 to 6
#: columns against ``np.copyto``'s 11.5, 12.3, 13.0, 14.1 and 15.5 ms
_COLUMNWISE_MAX_DIM = 6


def _copy_segment(dst: np.ndarray, src: np.ndarray) -> bool:
    """Write ``src`` into ``dst`` (its C-order, little-endian slot in the
    frame) and say whether the column-by-column path took it.

    The evaluate plane's coordinates arrive as ``out[:dim, :n].T``: the
    transpose of C-ordered rows, so each column is contiguous.  For a few
    columns, one contiguous read per column into a strided write beats
    numpy's generic transposing copy; past that, or for any other layout
    (contiguous, bool masks, big-endian), ``np.copyto`` does it.  Both
    release the interpreter lock while they copy."""
    if src.ndim == 2 and 1 < src.shape[1] <= _COLUMNWISE_MAX_DIM \
            and src.strides[0] == src.itemsize:
        for j in range(src.shape[1]):
            dst[:, j] = src[:, j]
        return True
    np.copyto(dst, src)
    return False


def encode_frame(payload, stats: dict | None = None) -> memoryview:
    """One binary frame for a JSON-ish payload whose arrays are numpy.

    Arrays serialize as raw little-endian bytes (C order); everything else
    rides in the JSON metadata header.  ``decode_frame`` is the exact
    inverse, dtype and shape included.

    The frame is built in one buffer: the preamble, header and length
    prefixes are packed into it, and each array is copied once, straight
    into its slice, with the interpreter lock released.  The buffer is a
    zero-filled ``bytearray``, so its fresh pages are first touched by one
    thread at a time, under the interpreter lock: when several workers
    first wrote fresh ``np.empty`` frames at once, a TPU v5e host running
    under gVisor stopped getting freed memory back, about 1.5 GB a second,
    and ran out within a minute.  The result is a read-only view of the buffer, so
    a cached blob can never change.  ``stats``, when given, receives
    ``frame`` (its length), ``copied`` (array bytes copied into it) and
    ``columnwise`` (segments copied column by column)."""
    segments: list[np.ndarray] = []
    stripped = _strip_arrays(payload, segments)
    header = {
        "payload": stripped,
        "segments": [{"dtype": a.dtype.name, "shape": list(a.shape)}
                     for a in segments],
    }
    head = json.dumps(header, default=str).encode()
    offset = 12 + len(head)
    total = offset + sum(_U32.size + a.nbytes for a in segments)
    buf = bytearray(total)
    struct.pack_into(f"<4sII{len(head)}s", buf, 0, MAGIC, VERSION,
                     len(head), head)
    frame = np.frombuffer(buf, np.uint8)
    columnwise = 0
    for arr in segments:
        _U32.pack_into(frame, offset, arr.nbytes)
        offset += _U32.size
        dst = frame[offset:offset + arr.nbytes].view(
            arr.dtype.newbyteorder("<")).reshape(arr.shape)
        columnwise += _copy_segment(dst, arr)
        offset += arr.nbytes
    if stats is not None:
        stats["frame"] = total
        stats["copied"] = sum(a.nbytes for a in segments)
        stats["columnwise"] = columnwise
    return memoryview(buf).toreadonly()


# -- decode ------------------------------------------------------------------

def _restore_arrays(obj, arrays: list[np.ndarray], used: list[bool]):
    if isinstance(obj, dict):
        if set(obj) == {"__nd__"}:
            idx = obj["__nd__"]
            if not isinstance(idx, int) or not 0 <= idx < len(arrays):
                raise WireFormatError(
                    f"frame header references segment {idx!r} of "
                    f"{len(arrays)}")
            used[idx] = True
            return arrays[idx]
        return {k: _restore_arrays(v, arrays, used) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_restore_arrays(v, arrays, used) for v in obj]
    return obj


def decode_frame(buf: bytes | bytearray | memoryview):
    """Decode one frame back to its payload.  Array segments come back as
    ``np.frombuffer`` views over ``buf`` (zero-copy) with the dtype and
    shape the header declares.  Raises :class:`WireFormatError` on any
    malformed, truncated, or version-unknown frame."""
    view = memoryview(buf)
    if len(view) < 12:
        raise WireFormatError(
            f"binary frame truncated: {len(view)} bytes < 12-byte preamble")
    if bytes(view[:4]) != MAGIC:
        raise WireFormatError(
            f"bad frame magic {bytes(view[:4])!r} (expected {MAGIC!r}) — "
            "not a repro binary frame")
    version = _U32.unpack_from(view, 4)[0]
    if version != VERSION:
        raise WireFormatError(
            f"unknown wire version {version} (this build speaks "
            f"{VERSION})")
    head_len = _U32.unpack_from(view, 8)[0]
    if head_len > _MAX_HEADER_BYTES:
        raise WireFormatError(f"frame header length {head_len} exceeds "
                              f"{_MAX_HEADER_BYTES} bytes")
    if 12 + head_len > len(view):
        raise WireFormatError(
            f"frame truncated inside header: need {12 + head_len} bytes, "
            f"have {len(view)}")
    try:
        header = json.loads(bytes(view[12:12 + head_len]))
    except ValueError as e:
        raise WireFormatError(f"frame header is not valid JSON: {e}") from e
    if not isinstance(header, dict) or "payload" not in header \
            or not isinstance(header.get("segments"), list):
        raise WireFormatError(
            "frame header must be an object with 'payload' and 'segments'")
    offset = 12 + head_len
    arrays: list[np.ndarray] = []
    for i, seg in enumerate(header["segments"]):
        if not isinstance(seg, dict):
            raise WireFormatError(f"segment {i} metadata is not an object")
        try:
            dtype = np.dtype(seg["dtype"])
            shape = tuple(int(s) for s in seg["shape"])
        except (KeyError, TypeError, ValueError) as e:
            raise WireFormatError(
                f"segment {i} carries bad dtype/shape metadata: {e}") from e
        if offset + 4 > len(view):
            raise WireFormatError(
                f"frame truncated before segment {i} length prefix")
        nbytes = _U32.unpack_from(view, offset)[0]
        offset += 4
        if nbytes > _MAX_SEGMENT_BYTES or offset + nbytes > len(view):
            raise WireFormatError(
                f"frame truncated inside segment {i}: declared {nbytes} "
                f"bytes, {len(view) - offset} remain")
        expect = dtype.itemsize * int(np.prod(shape, dtype=np.int64)) \
            if shape else dtype.itemsize
        if nbytes != expect:
            raise WireFormatError(
                f"segment {i} is {nbytes} bytes but dtype={dtype.name} "
                f"shape={list(shape)} needs {expect}")
        arr = np.frombuffer(view, dtype=dtype.newbyteorder("<"),
                            count=expect // dtype.itemsize,
                            offset=offset).reshape(shape)
        if arr.dtype.byteorder == ">":  # pragma: no cover — BE hosts only
            arr = arr.astype(dtype)
        arrays.append(arr)
        offset += nbytes
    if offset != len(view):
        raise WireFormatError(
            f"{len(view) - offset} trailing bytes after the last segment")
    used = [False] * len(arrays)
    payload = _restore_arrays(header["payload"], arrays, used)
    if not all(used):
        raise WireFormatError(
            "frame carries segments its payload never references")
    return payload


def decode_request(raw: bytes) -> dict:
    """A binary-framed *request* body: the decoded payload must be a JSON
    object (the same contract the JSON request path enforces)."""
    body = decode_frame(raw)
    if not isinstance(body, dict):
        raise WireFormatError("binary request body must frame a JSON object")
    return body


# -- streaming ---------------------------------------------------------------

def stream_chunk(frame: bytes | memoryview) -> bytes:
    """One cell of a binary sweep stream: u32 LE length prefix + frame."""
    return _U32.pack(len(frame)) + frame


def read_exact(read: Callable[[int], bytes], n: int) -> bytes:
    """Drain exactly ``n`` bytes from a sized-read callable (``http.client``
    responses may return short reads); b"" on clean EOF at a boundary,
    :class:`WireFormatError` on EOF mid-chunk."""
    chunks: list[bytes] = []
    got = 0
    while got < n:
        piece = read(n - got)
        if not piece:
            if not chunks:
                return b""
            raise WireFormatError(
                f"binary stream truncated: expected {n} bytes, got {got}")
        chunks.append(piece)
        got += len(piece)
    return b"".join(chunks)


def iter_stream(read: Callable[[int], bytes]):
    """Decode a length-prefixed frame stream until clean EOF, yielding one
    payload per frame.  A truncated prefix or frame raises
    :class:`WireFormatError` — close-delimited streams end exactly on a
    frame boundary or they are broken."""
    while True:
        prefix = read_exact(read, 4)
        if prefix == b"":
            return
        (length,) = _U32.unpack(prefix)
        frame = read_exact(read, length)
        if frame == b"" and length:
            raise WireFormatError(
                "binary stream truncated: frame body missing after prefix")
        yield decode_frame(frame)


# -- negotiation -------------------------------------------------------------

def wants_binary(accept: str | None, path: str = "",
                 content_type: str | None = None) -> bool:
    """Did the request ask for a binary response?  Any of: an ``Accept``
    header naming the binary media type, ``?format=binary`` in the URL, or
    a binary-framed request body (a client speaking binary understands
    binary).  Absent all three the answer stays JSON — old clients never
    see a byte they can't parse."""
    if accept and CONTENT_TYPE in accept:
        return True
    if content_type and content_type.startswith(CONTENT_TYPE):
        return True
    if "?" in path:
        from urllib.parse import parse_qs, urlsplit

        if parse_qs(urlsplit(path).query).get("format", [""])[0] == "binary":
            return True
    return False


def is_binary(content_type: str | None) -> bool:
    """Is a *response* Content-Type one of the binary framings?  The
    client's fallback test: an old server ignores the Accept header and
    answers JSON, which this returns False for."""
    return bool(content_type) and content_type.startswith(CONTENT_TYPE)


# -- response-bytes LRU ------------------------------------------------------

#: the most bytes of encoded responses a :class:`WireCache` holds, whatever
#: its entry bound: one binary batch answer reaches about 80 MB, so an
#: entry bound alone lets 256 of them hold 20 GB of the host's memory
MAX_CACHED_BYTES = 4 << 30


class WireCache:
    """LRU of encoded evaluate responses, keyed by the batch's resolved
    executable identity (per member: fingerprint × tier × λ-range/extent ×
    block × interpret) plus the wire format — the evaluate-plane mirror of
    the async frontend's derive blob cache.

    Entries are generation-stamped with the compile cache's eviction
    counter: once the compile cache rotates, cached blobs whose provenance
    says ``executable: hit`` may be stale, so they stop serving.  Entries
    also remember which artifact content addresses they depend on, so a
    ``DELETE /v1/artifact/<key>`` drops exactly the blobs that embedded
    that artifact's coordinates.  The oldest entries go once there are
    more than ``entries`` or they hold more than ``MAX_CACHED_BYTES``.
    Thread-safe: the threaded frontend hits it from many handler threads,
    the async one from loop + workers."""

    def __init__(self, entries: int = 256):
        self.entries = entries
        self.hits = 0
        self.misses = 0
        self._mu = threading.Lock()
        # cell -> (generation, artifact_keys, blob)
        self._cache: OrderedDict[tuple, tuple[int, tuple, bytes | memoryview]]
        self._cache = OrderedDict()
        self._bytes = 0

    def _drop(self, cell: tuple) -> None:
        entry = self._cache.pop(cell, None)
        if entry is not None:
            self._bytes -= len(entry[2])

    def get(self, cell: tuple,
            generation: int = 0) -> bytes | memoryview | None:
        with self._mu:
            hit = self._cache.get(cell)
            if hit is None or hit[0] != generation:
                if hit is not None:  # stale generation: drop eagerly
                    self._drop(cell)
                self.misses += 1
                return None
            self._cache.move_to_end(cell)
            self.hits += 1
            return hit[2]

    def put(self, cell: tuple, blob: bytes | memoryview,
            generation: int = 0, artifact_keys: tuple = ()) -> None:
        with self._mu:
            self._drop(cell)
            if len(blob) > MAX_CACHED_BYTES:
                return
            self._cache[cell] = (generation, artifact_keys, blob)
            self._bytes += len(blob)
            while len(self._cache) > self.entries \
                    or self._bytes > MAX_CACHED_BYTES:
                self._drop(next(iter(self._cache)))

    def invalidate_artifact(self, key: str) -> None:
        with self._mu:
            stale = [cell for cell, (_, keys, _) in self._cache.items()
                     if key in keys]
            for cell in stale:
                self._drop(cell)

    def clear(self) -> None:
        with self._mu:
            self._cache.clear()
            self._bytes = 0

    def stats_dict(self) -> dict:
        with self._mu:
            return {"entries": len(self._cache), "capacity": self.entries,
                    "bytes": self._bytes,
                    "hits": self.hits, "misses": self.misses}
