"""The sidecar wire's columnar payload: the three columns of a batch
(public keys, messages, signatures) as three `Column` messages, never a
field a triple. `sidecar/service.py`'s module docstring ("Wire format",
"Columns") is the schema and the list of what the decoder refuses; both
methods (`BatchVerify`, `BatchVerifyChunk`) and both ends use these two
functions and nothing else. A commit's sign bytes differ in length with
their timestamps' varints, so a lengths array of 10,000 entries is the
common case, and it is packed and unpacked as arrays, not entry by entry."""

from __future__ import annotations

import numpy as np

from cometbft_tpu.wire import proto


def _pack_uvarints(v: np.ndarray) -> bytes:
    """`b"".join(map(proto.encode_uvarint, v))` for an int64 array."""
    widest = max(1, -(-int(v.max(initial=0)).bit_length() // 7))
    septet = np.arange(widest)
    v = v.reshape(-1, 1)
    width = 1 + ((v >> 7 * septet[1:]) > 0).sum(axis=1, keepdims=True)
    out = (((v >> 7 * septet) & 0x7F) | ((septet < width - 1) << 7)).astype(np.uint8)
    # sign bytes of 128 to 16,383 bytes: two bytes every length, nothing to drop
    return (out if width.min(initial=widest) == widest else out[septet < width]).tobytes()


def _unpack_uvarints(buf: bytes, n: int) -> np.ndarray:
    """Exactly `n` packed uvarints of at most five bytes each (a length is
    under 2**35), as int64; ValueError for any other buffer."""
    b = np.frombuffer(buf, np.uint8)
    last = np.flatnonzero(b < 0x80)  # where each varint ends
    if len(last) != n or (len(b) and b[-1] >= 0x80):
        raise ValueError(f"{len(last)} lengths for {n} entries")
    first = np.concatenate(([0], last[:-1] + 1))[:n]
    width = last - first + 1
    if width.max(initial=0) > 5:
        raise ValueError("a length of more than five bytes of varint")
    shift = 7 * (np.arange(len(b)) - np.repeat(first, width))
    return np.add.reduceat((b & 0x7F).astype(np.int64) << shift, first)


def encode_columns(first: int, pubs, msgs, sigs) -> tuple[bytes, int]:
    """Fields `first`..`first + 2` of a BatchVerifyReq or ChunkReq (module
    docstring of `sidecar/service.py`: Column), and how many of the three
    columns went ragged."""
    parts, ragged = [], 0
    for num, col in enumerate((pubs, msgs, sigs), first):
        sizes = set(map(len, col))
        stride = sizes.pop() if len(sizes) == 1 else 0
        head = proto.field_varint(1, len(col)) + proto.field_varint(2, stride)
        if stride:
            total = len(col) * stride
        else:
            ragged += 1
            lens = np.fromiter(map(len, col), np.int64, len(col))
            total = int(lens.sum())
            head += proto.field_bytes(3, _pack_uvarints(lens))
        if total:
            head += proto.tag(4, proto.WT_LEN) + proto.encode_uvarint(total)
        parts.append(proto.tag(num, proto.WT_LEN) + proto.encode_uvarint(len(head) + total) + head)
        parts.extend(col)
    return b"".join(parts), ragged


def decode_columns(fields: dict, first: int) -> tuple[list, list, list, int]:
    """`encode_columns` back: three lists of `bytes` of one length, and how
    many arrived ragged. Raises ValueError on every column the schema says
    the decoder refuses; never returns a shorter batch."""
    cols, ragged = [], 0
    for num in range(first, first + 3):
        f = proto.decode_fields(proto.get_bytes(fields, num))
        n, stride = proto.get_uvarint(f, 1), proto.get_uvarint(f, 2)
        lens, data = proto.get_bytes(f, 3), proto.get_bytes(f, 4)
        if stride:
            if lens:
                raise ValueError(f"column {num}: a stride and a lengths array")
            if n * stride != len(data):
                raise ValueError(f"column {num}: {n} x {stride} is not its {len(data)} bytes")
            cols.append(np.frombuffer(data, np.dtype((np.void, stride))).tolist())
            continue
        ragged += 1
        lens = _unpack_uvarints(lens, n)
        # no length over the blob's, so the sum cannot overflow
        if lens.max(initial=0) > len(data) or int(lens.sum()) != len(data):
            raise ValueError(f"column {num}: lengths do not sum to its {len(data)} bytes")
        ends = np.cumsum(lens).tolist()
        cols.append([data[a:b] for a, b in zip([0] + ends, ends)])
    if not len(cols[0]) == len(cols[1]) == len(cols[2]):
        raise ValueError("pubs/msgs/sigs length mismatch")
    return cols[0], cols[1], cols[2], ragged
