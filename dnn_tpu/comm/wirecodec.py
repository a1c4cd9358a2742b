"""Zero-copy proto3 wire codec for the comm hot path.

The reference transport round-trips every activation through THREE host
copies per direction: `arr.tobytes()` (copy 1), protobuf's internal
bytes-field store + `SerializeToString` (copy 2), and
`np.frombuffer(...).copy()` on the receiver (copy 3). Python protobuf cannot take a memoryview for a bytes
field, so the fix is one layer down: this module hand-assembles and
hand-parses the proto3 *wire format* of the three Tensor-carrying
messages (`Tensor`, `TensorRequest`, `TensorResponse` —
dnn_tpu/comm/wire.proto), which the repo can do because every gRPC
method is registered with EXPLICIT serializer callables
(comm/service._handlers, comm/client) rather than generated stubs.

Wire compatibility is byte-level: the serializer emits valid proto3
(length-delimited fields, packed repeated int32 shape — exactly what
protobuf itself emits for these messages), and the parser is a tolerant
field scanner that skips unknown fields and accepts both packed and
non-packed shape encodings, so reference peers running real protobuf
interoperate unchanged (pinned by tests/test_transport.py golden
round-trips against wire_pb2).

Copy accounting: the ONLY payload copy on the send side is the final
`b"".join` into the gRPC message buffer (unavoidable — the transport
owns its buffer), and the receive side is a `np.frombuffer` VIEW over
the gRPC message bytes (zero copies; the array keeps the buffer alive
via .base). Payload bytes that had to be materialized anyway —
non-contiguous arrays, foreign endianness — are counted into
`comm.payload_bytes_copied_total`, so a zero counter next to a nonzero
`comm.payload_bytes_total` is the proof the hot path stayed zero-copy.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from dnn_tpu import native as _native

BytesLike = Union[bytes, memoryview]

# wire types
_VARINT = 0
_I64 = 1
_LEN = 2
_I32 = 5


def _encode_varint(n: int) -> bytes:
    if n < 0:
        # int32/int64 negative values ride as 64-bit two's complement
        n &= 0xFFFFFFFFFFFFFFFF
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _decode_varint(buf, pos: int) -> Tuple[int, int]:
    shift = 0
    val = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint in wire payload")
        b = buf[pos]
        pos += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint overflows 64 bits")


def _scan(buf: memoryview):
    """Yield (field_no, wire_type, value) over one message's wire bytes.
    LEN fields yield a zero-copy memoryview slice; varint/fixed yield
    ints. Unknown wire types fail loud (corrupt frame, not a field to
    skip)."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = _decode_varint(buf, pos)
        field, wt = key >> 3, key & 7
        if wt == _VARINT:
            val, pos = _decode_varint(buf, pos)
        elif wt == _LEN:
            ln, pos = _decode_varint(buf, pos)
            if pos + ln > n:
                raise ValueError("truncated length-delimited field")
            val = buf[pos:pos + ln]
            pos += ln
        elif wt == _I64:
            val = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wt == _I32:
            val = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wt} (field {field})")
        yield field, wt, val


def _int32(v: int) -> int:
    v &= 0xFFFFFFFF
    return v - (1 << 32) if v >= (1 << 31) else v


# ----------------------------------------------------------------------
# message shims (duck-typed stand-ins for the wire_pb2 classes on the
# paths the servers/clients actually touch)
# ----------------------------------------------------------------------

class Tensor:
    """wire.proto `Tensor`. `tensor_data` may be a memoryview (zero-copy
    slice of the inbound gRPC buffer, or the outbound array's own
    buffer); consumers treat it as read-only bytes."""

    __slots__ = ("tensor_data", "shape", "dtype", "crc32c")

    def __init__(self, tensor_data: BytesLike = b"",
                 shape: Sequence[int] = (), dtype: str = "",
                 crc32c: Optional[int] = None):
        self.tensor_data = tensor_data
        self.shape = list(shape)
        self.dtype = dtype
        self.crc32c = crc32c

    def HasField(self, name: str) -> bool:  # noqa: N802 — pb API
        if name != "crc32c":
            raise ValueError(f"Tensor has no presence field {name!r}")
        return self.crc32c is not None

    def _parts(self) -> List[BytesLike]:
        parts: List[BytesLike] = []
        ln = len(self.tensor_data)
        if ln:  # proto3 canonical form omits empty scalar fields
            parts.append(b"\x0a" + _encode_varint(ln))
            parts.append(self.tensor_data)
        if self.shape:
            packed = b"".join(_encode_varint(int(s)) for s in self.shape)
            parts.append(b"\x12" + _encode_varint(len(packed)) + packed)
        if self.dtype:
            d = self.dtype.encode()
            parts.append(b"\x1a" + _encode_varint(len(d)) + d)
        if self.crc32c is not None:
            parts.append(b"\x20" + _encode_varint(self.crc32c & 0xFFFFFFFF))
        return parts

    def ByteSize(self) -> int:  # noqa: N802 — pb API
        return sum(len(p) for p in self._parts())


def _parse_tensor(buf: memoryview) -> Tensor:
    t = Tensor()
    for field, wt, val in _scan(buf):
        if field == 1 and wt == _LEN:
            t.tensor_data = val
        elif field == 2:
            if wt == _LEN:  # packed repeated int32 (protobuf's default)
                pos = 0
                while pos < len(val):
                    v, pos = _decode_varint(val, pos)
                    t.shape.append(_int32(v))
            elif wt == _VARINT:  # non-packed encoder
                t.shape.append(_int32(val))
        elif field == 3 and wt == _LEN:
            t.dtype = bytes(val).decode()
        elif field == 4 and wt == _VARINT:
            t.crc32c = val & 0xFFFFFFFF
    return t


class TensorRequest:
    __slots__ = ("request_id", "tensor", "_wire_len")

    def __init__(self, request_id: str = "", tensor: Optional[Tensor] = None):
        self.request_id = request_id
        self.tensor = tensor if tensor is not None else Tensor()
        self._wire_len: Optional[int] = None

    def _parts(self) -> List[BytesLike]:
        parts: List[BytesLike] = []
        if self.request_id:
            r = self.request_id.encode()
            parts.append(b"\x0a" + _encode_varint(len(r)) + r)
        sub = self.tensor._parts()
        parts.append(b"\x12" + _encode_varint(sum(len(p) for p in sub)))
        parts.extend(sub)
        return parts

    def ByteSize(self) -> int:  # noqa: N802 — pb API
        if self._wire_len is not None:
            return self._wire_len
        return sum(len(p) for p in self._parts())


class TensorResponse:
    __slots__ = ("status", "result_tensor", "_wire_len")

    def __init__(self, status: str = "",
                 result_tensor: Optional[Tensor] = None):
        self.status = status
        self.result_tensor = result_tensor
        self._wire_len: Optional[int] = None

    def HasField(self, name: str) -> bool:  # noqa: N802 — pb API
        if name != "result_tensor":
            raise ValueError(f"TensorResponse has no presence field {name!r}")
        return self.result_tensor is not None

    def _parts(self) -> List[BytesLike]:
        parts: List[BytesLike] = []
        if self.status:
            s = self.status.encode()
            parts.append(b"\x0a" + _encode_varint(len(s)) + s)
        if self.result_tensor is not None:
            sub = self.result_tensor._parts()
            parts.append(b"\x12" + _encode_varint(sum(len(p) for p in sub)))
            parts.extend(sub)
        return parts

    def ByteSize(self) -> int:  # noqa: N802 — pb API
        if self._wire_len is not None:
            return self._wire_len
        return sum(len(p) for p in self._parts())


# ----------------------------------------------------------------------
# gRPC (de)serializer callables
# ----------------------------------------------------------------------

def serialize_request(msg) -> bytes:
    """TensorRequest -> wire bytes. Accepts the shim (single-join
    zero-intermediate path) or a real wire_pb2 message (interop /
    legacy call sites)."""
    if isinstance(msg, TensorRequest):
        return b"".join(msg._parts())
    return msg.SerializeToString()


def serialize_response(msg) -> bytes:
    if isinstance(msg, TensorResponse):
        return b"".join(msg._parts())
    return msg.SerializeToString()


def parse_request(data: bytes) -> TensorRequest:
    req = TensorRequest()
    buf = memoryview(data)
    for field, wt, val in _scan(buf):
        if field == 1 and wt == _LEN:
            req.request_id = bytes(val).decode()
        elif field == 2 and wt == _LEN:
            req.tensor = _parse_tensor(val)
    req._wire_len = len(data)
    return req


def parse_response(data: bytes) -> TensorResponse:
    resp = TensorResponse()
    buf = memoryview(data)
    for field, wt, val in _scan(buf):
        if field == 1 and wt == _LEN:
            resp.status = bytes(val).decode()
        elif field == 2 and wt == _LEN:
            resp.result_tensor = _parse_tensor(val)
    resp._wire_len = len(data)
    return resp


# ----------------------------------------------------------------------
# zero-copy tensor payload helpers
# ----------------------------------------------------------------------

def tensor_payload(arr) -> Tuple[BytesLike, Tuple[int, ...], str, int]:
    """array -> (payload_view, shape, dtype_name, bytes_copied).

    Contiguous little-endian arrays (the hot path: every jit output)
    yield their OWN buffer as a memoryview — zero copies here; the one
    remaining copy is the final join into the gRPC message buffer.
    Non-contiguous or big-endian inputs must materialize (counted)."""
    a = np.asarray(arr)
    copied = 0
    if a.dtype.byteorder == ">":
        a = a.astype(a.dtype.newbyteorder("<"))
        copied = a.nbytes
    shape = tuple(a.shape)  # before ascontiguousarray (0-d promotion)
    if not a.flags.c_contiguous:
        a = np.ascontiguousarray(a)
        copied = a.nbytes
    # memoryview over the array's buffer, flattened to 1-D bytes: the
    # uint8 reinterpret-view (no data movement) also covers dtypes the
    # buffer protocol rejects (ml_dtypes bfloat16). The view keeps the
    # array alive; 0-d reshapes to 1-d first.
    view = memoryview(a.reshape(-1).view(np.uint8))
    return view, shape, a.dtype.name, copied


def tensor_view(msg, *, check_crc: bool = True) -> np.ndarray:
    """Tensor message -> zero-copy (read-only) ndarray view over the
    message's payload bytes. Length-validated; crc32c verified when
    declared and the native codec is built (same contract as the old
    copying decoder)."""
    from dnn_tpu.io.serialization import PayloadCorruptError, _np_dtype

    if check_crc and msg.HasField("crc32c"):
        from dnn_tpu.native import crc32c, native_available

        if native_available():
            got = crc32c(msg.tensor_data)
            if got != msg.crc32c:
                raise PayloadCorruptError(
                    f"tensor payload corrupt: crc32c {got:#010x} != "
                    f"declared {msg.crc32c:#010x}")
    dt = _np_dtype(msg.dtype)
    shape = tuple(int(s) for s in msg.shape)
    expect = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
    if len(msg.tensor_data) != expect:
        raise ValueError(
            f"tensor payload is {len(msg.tensor_data)} bytes but shape "
            f"{shape} dtype {msg.dtype} needs {expect}")
    return np.frombuffer(msg.tensor_data, dtype=dt).reshape(shape)


def make_tensor(arr, *, crc: bool = True) -> Tensor:
    """array -> Tensor shim with a zero-copy payload view (and the
    payload-copy counter fed when the input forced a materialization).
    Checksummed under the same policy as the legacy encoder: only when
    the native codec is built (Python crc is a per-byte loop)."""
    from dnn_tpu import obs
    from dnn_tpu.utils.metrics import labeled

    view, shape, dtype, copied = tensor_payload(arr)
    if copied:
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("comm.payload_bytes_copied_total",
                          reason="noncontiguous"), copied)
    checksum = None
    if crc:
        from dnn_tpu.native import crc32c, native_available

        if native_available():
            checksum = crc32c(view)
    return Tensor(tensor_data=view, shape=shape, dtype=dtype,
                  crc32c=checksum)


def make_token_tensor(tok: int) -> Tensor:
    """ONE int32 token -> the Tensor `make_tensor(np.asarray([tok],
    np.int32))` gives, byte for byte on the wire, from the integer: its
    four little-endian bytes, shape (1,), dtype "int32", and crc32c
    declared exactly when `make_tensor` would declare it (the native
    codec is built) with the same value, through the table in Python. A
    streamed token's message is built once a token on the serving
    daemon's event-loop thread (lm_server.GenerateStream): no array, no
    import, no metrics lookup and no call that releases the interpreter
    lock on that path."""
    data = tok.to_bytes(4, "little", signed=True)
    return Tensor(data, (1,), "int32",
                  _native.crc32c_table(data)
                  if _native.native_available() else None)
