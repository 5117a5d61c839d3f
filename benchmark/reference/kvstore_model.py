"""Plain reference: the built-in kvstore application as a dict
(reference abci/example/kvstore/kvstore.go): a tx `k=v` stores v under
k, a tx without `=` stores itself under itself, the app hash is the
8-byte big-endian count of delivered txs. Imports nothing of the
program."""

from __future__ import annotations

import struct


class KVStoreModel:
    def __init__(self):
        self.values: dict[bytes, bytes] = {}
        self.size = 0

    def deliver(self, tx: bytes) -> None:
        key, sep, value = tx.partition(b"=")
        if not sep:
            key = value = tx
        self.values[key] = value
        self.size += 1

    @staticmethod
    def key_of(tx: bytes) -> bytes:
        key, sep, _ = tx.partition(b"=")
        return key if sep else tx

    def app_hash(self) -> bytes:
        return struct.pack(">Q", self.size)
