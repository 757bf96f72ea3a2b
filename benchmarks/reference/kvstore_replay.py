"""The benchmark's application yardstick: a dict replay of `key=value`
transactions, giving what the kvstore application defines for them.

Written from the reference's `abci/example/kvstore/kvstore.go`, not copied
from `cometbft_tpu/abci/example/kvstore.py`, and kept under the benchmark's
own directory so that no later PR can move what `correct` is decided
against. Nothing here imports the program.

    DeliverTx   `key=value` sets key to value; a tx with no `=` sets tx to tx
    Commit      app hash = Go's binary.PutVarint(count of txs delivered so
                far) in an 8-byte buffer (zigzag, little-endian base 128)
    result      code 0, no data, no gas: its deterministic encoding
                (types/results.go: code=1, data=2, gas_wanted=5, gas_used=6,
                proto3, so zero fields are left out) is the empty string
"""

from __future__ import annotations


def app_hash_after(txs_delivered: int) -> bytes:
    zigzag = txs_delivered << 1  # a count is never negative
    out = bytearray()
    while True:
        low, zigzag = zigzag & 0x7F, zigzag >> 7
        out.append(low | 0x80 if zigzag else low)
        if not zigzag:
            break
    return bytes(out) + b"\x00" * (8 - len(out))


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def result_leaf(code: int = 0, data: bytes = b"", gas_wanted: int = 0, gas_used: int = 0) -> bytes:
    """A DeliverTx result as `LastResultsHash` hashes it."""
    out = b""
    if code:
        out += b"\x08" + _uvarint(code)
    if data:
        out += b"\x12" + _uvarint(len(data)) + data
    if gas_wanted:
        out += b"\x28" + _uvarint(gas_wanted)
    if gas_used:
        out += b"\x30" + _uvarint(gas_used)
    return out


def replay(blocks: list[list[bytes]]) -> tuple[list[bytes], dict[bytes, bytes]]:
    """(the app hash after each block, the final key-value map)."""
    kv: dict[bytes, bytes] = {}
    hashes = []
    delivered = 0
    for txs in blocks:
        for tx in txs:
            key, sep, value = tx.partition(b"=")
            kv[key] = value if sep else tx
            delivered += 1
        hashes.append(app_hash_after(delivered))
    return hashes, kv
