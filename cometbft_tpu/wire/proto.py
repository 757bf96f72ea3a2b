"""Minimal deterministic protobuf encoder/decoder.

Implements exactly the subset of proto3 wire format the canonical data
structures need (reference wire types: proto/tendermint/**). Proto3 rules
honored: default-valued scalar fields are omitted; fields are emitted in
ascending field-number order; `bytes`/`string`/sub-messages are
length-delimited; sfixed64 for canonical height/round (types/canonical.go).
"""

from __future__ import annotations

import struct

# Wire types
WT_VARINT = 0
WT_FIXED64 = 1
WT_LEN = 2
WT_FIXED32 = 5


_UVARINT_1B = [bytes((v,)) for v in range(0x80)]


def encode_uvarint(n: int) -> bytes:
    if n < 0x80:
        # Single-byte fast path: field tags and small lengths dominate call
        # volume on the hot sign-bytes/encode paths.
        if n < 0:
            raise ValueError("uvarint cannot encode negative")
        return _UVARINT_1B[n]
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def decode_uvarint(buf: bytes, pos: int = 0) -> tuple[int, int]:
    """Max 10 bytes / 64 bits, matching Go's binary.Uvarint and protobuf."""
    result = 0
    shift = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint")
        if shift >= 70:
            raise ValueError("varint too long")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            if result >= 1 << 64:
                raise ValueError("varint overflows 64 bits")
            return result, pos
        shift += 7


def encode_varint_signed(n: int) -> bytes:
    """proto `int64`: negative values use 10-byte two's complement varint."""
    if n < 0:
        n += 1 << 64
        if n >= 1 << 63:
            # Every int64 below zero is ten bytes: unrolled, since proposer
            # priorities below zero fill a validator set's encoding.
            return bytes((
                n & 0x7F | 0x80, n >> 7 & 0x7F | 0x80, n >> 14 & 0x7F | 0x80,
                n >> 21 & 0x7F | 0x80, n >> 28 & 0x7F | 0x80,
                n >> 35 & 0x7F | 0x80, n >> 42 & 0x7F | 0x80,
                n >> 49 & 0x7F | 0x80, n >> 56 & 0x7F | 0x80, n >> 63,
            ))
    return encode_uvarint(n)


def decode_varint_signed(buf: bytes, pos: int = 0) -> tuple[int, int]:
    v, pos = decode_uvarint(buf, pos)
    if v >= 1 << 63:
        v -= 1 << 64
    return v, pos


def canonical_varint(buf: bytes, pos: int) -> tuple[int, int]:
    """An int64 field's value at buf[pos] read only in the form the encoders
    above write it: minimal, at most ten bytes, and not 0 (a zero field is
    omitted). Returns the value as get_varint reads it (ten bytes below
    zero) and the position after it. Raises ValueError on any other form,
    IndexError where buf ends first: a caller that walks canonical records
    hands such a record to the generic decoder instead."""
    b = buf[pos]
    if b < 0x80:
        if not b:
            raise ValueError("zero field written")
        return b, pos + 1
    v = b & 0x7F
    shift = 7
    while True:
        pos += 1
        b = buf[pos]
        v |= (b & 0x7F) << shift
        if b < 0x80:
            break
        shift += 7
        if shift > 63:
            raise ValueError("varint too long")
    if not b:
        raise ValueError("non-minimal varint")
    if v >> 64:
        raise ValueError("varint overflows 64 bits")
    if v >> 63:
        v -= 1 << 64
    return v, pos + 1


def encode_zigzag(n: int) -> bytes:
    """proto `sint64`."""
    return encode_uvarint((n << 1) ^ (n >> 63))


def tag(field_num: int, wire_type: int) -> bytes:
    return encode_uvarint((field_num << 3) | wire_type)


def field_varint(field_num: int, value: int, *, emit_default: bool = False) -> bytes:
    if value == 0 and not emit_default:
        return b""
    return tag(field_num, WT_VARINT) + encode_varint_signed(value)


def field_bool(field_num: int, value: bool, *, emit_default: bool = False) -> bytes:
    if not value and not emit_default:
        return b""
    return tag(field_num, WT_VARINT) + (b"\x01" if value else b"\x00")


def field_sfixed64(field_num: int, value: int, *, emit_default: bool = False) -> bytes:
    if value == 0 and not emit_default:
        return b""
    return tag(field_num, WT_FIXED64) + struct.pack("<q", value)


def field_fixed64(field_num: int, value: int, *, emit_default: bool = False) -> bytes:
    if value == 0 and not emit_default:
        return b""
    return tag(field_num, WT_FIXED64) + struct.pack("<Q", value)


def field_bytes(field_num: int, value: bytes, *, emit_default: bool = False) -> bytes:
    if not value and not emit_default:
        return b""
    return tag(field_num, WT_LEN) + encode_uvarint(len(value)) + value


def field_string(field_num: int, value: str, *, emit_default: bool = False) -> bytes:
    return field_bytes(field_num, value.encode("utf-8"), emit_default=emit_default)


def field_message(field_num: int, encoded: bytes | None, *, emit_empty: bool = False) -> bytes:
    """A sub-message field. None ⇒ absent. Empty-encoded messages are still
    emitted when emit_empty (gogoproto non-nullable semantics)."""
    if encoded is None:
        return b""
    if not encoded and not emit_empty:
        return b""
    return tag(field_num, WT_LEN) + encode_uvarint(len(encoded)) + encoded


def encode_bytes_len_prefixed(bz: bytes) -> bytes:
    """uvarint length prefix + raw bytes (reference: types/encoding_helper.go
    cdcEncode-style helpers / libs protoio delimited writing)."""
    return encode_uvarint(len(bz)) + bz


def length_delimited(encoded: bytes) -> bytes:
    """Length-delimited framing of a full message (protoio.MarshalDelimited),
    used for canonical vote/proposal sign bytes (types/vote.go VoteSignBytes)."""
    return encode_uvarint(len(encoded)) + encoded


# ---------------------------------------------------------------------------
# Decoding: a tolerant field walker. Returns {field_num: [raw values]} where a
# raw value is int (varint), bytes (len-delimited) or 8/4-byte packed.


def decode_fields(buf: bytes) -> dict[int, list]:
    fields: dict[int, list] = {}
    pos = 0
    n = len(buf)
    while pos < n:
        # A key or a length below 0x80 is one byte, read in place: a
        # commit's or a validator set's thousands of records are all so.
        key = buf[pos]
        if key < 0x80:
            pos += 1
        else:
            key, pos = decode_uvarint(buf, pos)
        fnum, wt = key >> 3, key & 7
        if wt == WT_LEN:
            if pos < n and buf[pos] < 0x80:
                ln = buf[pos]
                pos += 1
            else:
                ln, pos = decode_uvarint(buf, pos)
            if pos + ln > n:
                raise ValueError("truncated length-delimited field")
            val = buf[pos : pos + ln]
            pos += ln
        elif wt == WT_VARINT:
            val, pos = decode_uvarint(buf, pos)
        elif wt == WT_FIXED64:
            if pos + 8 > n:
                raise ValueError("truncated fixed64")
            val = buf[pos : pos + 8]
            pos += 8
        elif wt == WT_FIXED32:
            if pos + 4 > n:
                raise ValueError("truncated fixed32")
            val = buf[pos : pos + 4]
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        vals = fields.get(fnum)
        if vals is None:
            fields[fnum] = [val]
        else:
            vals.append(val)
    return fields


# Getters raise ValueError on wire-type confusion (a varint where bytes were
# expected, or vice versa): adversarial inputs must fail decode cleanly, not
# surface AttributeError/struct.error from deeper in the stack.


def get_varint(fields: dict, num: int, default: int = 0) -> int:
    vals = fields.get(num)
    if not vals:
        return default
    v = vals[-1]
    if not isinstance(v, int):
        raise ValueError(f"field {num}: expected varint, got length-delimited")
    if v >= 1 << 63:
        v -= 1 << 64
    return v


def get_uvarint(fields: dict, num: int, default: int = 0) -> int:
    vals = fields.get(num)
    if not vals:
        return default
    v = vals[-1]
    if not isinstance(v, int):
        raise ValueError(f"field {num}: expected varint, got length-delimited")
    return v


def get_bool(fields: dict, num: int) -> bool:
    return bool(get_uvarint(fields, num, 0))


def get_bytes(fields: dict, num: int, default: bytes = b"") -> bytes:
    vals = fields.get(num)
    if not vals:
        return default
    v = vals[-1]
    if not isinstance(v, bytes):
        raise ValueError(f"field {num}: expected length-delimited, got varint")
    return v


def get_string(fields: dict, num: int, default: str = "") -> str:
    vals = fields.get(num)
    if not vals:
        return default
    v = vals[-1]
    if not isinstance(v, bytes):
        raise ValueError(f"field {num}: expected length-delimited, got varint")
    try:
        return v.decode("utf-8")
    except UnicodeDecodeError:
        raise ValueError(f"field {num}: invalid utf-8 string")


def get_sfixed64(fields: dict, num: int, default: int = 0) -> int:
    vals = fields.get(num)
    if not vals:
        return default
    v = vals[-1]
    if not isinstance(v, bytes) or len(v) != 8:
        raise ValueError(f"field {num}: expected fixed64")
    return struct.unpack("<q", v)[0]


def get_repeated_bytes(fields: dict, num: int) -> list[bytes]:
    vals = fields.get(num, [])
    if any(not isinstance(v, bytes) for v in vals):
        raise ValueError(f"field {num}: expected length-delimited, got varint")
    return list(vals)


def decode_records(records, walk, decode) -> tuple[list, int]:
    """Each record as `walk` reads it, where it is in the one form `walk`
    takes (it raises ValueError, or IndexError, on any other), else as the
    generic `decode` reads it. `walk` returns only what `decode` returns for
    the same bytes, so the result is `decode`'s, exceptions included.
    Returns the objects, in order, and how many went to `decode`."""
    out = []
    add = out.append
    generic = 0
    for r in records:
        try:
            add(walk(r))
            continue
        except (IndexError, ValueError):
            pass
        add(decode(r))
        generic += 1
    return out, generic


def get_repeated_uvarint(fields: dict, num: int) -> list[int]:
    """Repeated uvarint field, accepting both unpacked (one varint per tag)
    and proto3 packed (one length-delimited run of varints) encodings."""
    out: list[int] = []
    for v in fields.get(num, []):
        if isinstance(v, int):
            out.append(v)
        else:  # packed: bytes holding consecutive varints
            pos = 0
            while pos < len(v):
                val, pos = decode_uvarint(v, pos)
                out.append(val)
    return out
