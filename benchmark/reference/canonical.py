"""Plain reference: canonical precommit sign bytes and validator
addresses, written from the reference's `types/canonical.go` and
`proto/tendermint/types/canonical.proto` with no import of the program.

  CanonicalVote { type=1 varint; height=2 sfixed64; round=3 sfixed64;
                  block_id=4; timestamp=5; chain_id=6 }
  CanonicalBlockID { hash=1; part_set_header=2 {total=1; hash=2} }
  Timestamp { seconds=1; nanos=2 }

proto3: zero scalars are left out; the whole message is length-
delimited. The benchmark signs THESE bytes, so a commit the program
accepts shows that the program (on the device: its template + patch
assembly) derived the same bytes.
"""

from __future__ import annotations

import hashlib
import struct

PRECOMMIT = 2


def varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field_varint(field: int, v: int) -> bytes:
    return b"" if v == 0 else varint(field << 3) + varint(v)


def _field_bytes(field: int, b: bytes) -> bytes:
    return varint((field << 3) | 2) + varint(len(b)) + b


def vote_sign_parts(chain_id: str, height: int, round_: int,
                    block_hash: bytes, parts_total: int,
                    parts_hash: bytes) -> tuple[bytes, bytes]:
    """(bytes before the timestamp field, bytes after it) of a
    precommit for a block: the same for every validator of a commit."""
    pre = _field_varint(1, PRECOMMIT)
    pre += varint((2 << 3) | 1) + struct.pack("<q", height)
    if round_:
        pre += varint((3 << 3) | 1) + struct.pack("<q", round_)
    psh = _field_varint(1, parts_total) + _field_bytes(2, parts_hash)
    pre += _field_bytes(4, _field_bytes(1, block_hash) + _field_bytes(2, psh))
    return pre, _field_bytes(6, chain_id.encode())


def with_timestamp(pre: bytes, suf: bytes, time_ns: int) -> bytes:
    ts = (_field_varint(1, time_ns // 1_000_000_000)
          + _field_varint(2, time_ns % 1_000_000_000))
    body = pre + (_field_bytes(5, ts) if time_ns else b"") + suf
    return varint(len(body)) + body


def address(pub_key: bytes) -> bytes:
    """reference crypto/ed25519: SHA-256(pubkey)[:20]."""
    return hashlib.sha256(pub_key).digest()[:20]
