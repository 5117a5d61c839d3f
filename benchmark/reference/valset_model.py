"""Plain reference: a validator set that moves, as a dict of public key
-> voting power, written from the reference's `state/execution.go`
(`updateState`: block H's validator updates become `NextValidators`
of the state after H, so they are in force from height H+2),
`types/validator_set.go` (order: voting power descending, then address
ascending; `Hash`: the merkle root over each validator's
`SimpleValidator` encoding), `crypto/merkle/tree.go` (RFC 6962 hashes),
`types/time/time.go` (`WeightedMedian`) and
`abci/example/kvstore/persistent_kvstore.go` (`val:<pubkey hex>!<power>`
txs, which change the set and write no key). Imports nothing of the
program.
"""

from __future__ import annotations

import hashlib

from benchmark.reference import canonical
from benchmark.reference.kvstore_model import KVStoreModel

VAL_TX_PREFIX = b"val:"


def val_tx(pub_key: bytes, power: int) -> bytes:
    return VAL_TX_PREFIX + f"{pub_key.hex()}!{power}".encode()


def parse_val_tx(tx: bytes) -> tuple[bytes, int] | None:
    """(public key, power) of a validator tx, None of any other."""
    if not tx.startswith(VAL_TX_PREFIX):
        return None
    key_hex, _, power = tx[len(VAL_TX_PREFIX):].partition(b"!")
    return bytes.fromhex(key_hex.decode()), int(power)


class PersistentKVStoreModel(KVStoreModel):
    """The kvstore dict model's sibling for the persistent app: a
    `val:` tx writes no key and is not counted in the app hash."""

    def deliver(self, tx: bytes) -> None:
        if not tx.startswith(VAL_TX_PREFIX):
            super().deliver(tx)


# ------------------------------------------------------------- the set


def ordered(powers: dict[bytes, int]) -> list[tuple[bytes, int]]:
    """[(public key, power)] in validator-set order."""
    return sorted(powers.items(),
                  key=lambda kv: (-kv[1], canonical.address(kv[0])))


def simple_validator(pub_key: bytes, power: int) -> bytes:
    """SimpleValidator { PublicKey pub_key = 1; int64 voting_power = 2 }
    with PublicKey { oneof sum { bytes ed25519 = 1 } }."""
    key = b"\x0a" + canonical.varint(len(pub_key)) + pub_key
    out = b"\x0a" + canonical.varint(len(key)) + key
    if power:
        out += b"\x10" + canonical.varint(power)
    return out


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC 6962: leaf = H(0x00 | x), inner = H(0x01 | left | right),
    split at the largest power of two below the count; H("") if empty."""
    if not leaves:
        return hashlib.sha256(b"").digest()
    level = [hashlib.sha256(b"\x00" + x).digest() for x in leaves]

    def root(lo: int, hi: int) -> bytes:
        if hi - lo == 1:
            return level[lo]
        k = 1 << ((hi - lo - 1).bit_length() - 1)
        return hashlib.sha256(
            b"\x01" + root(lo, lo + k) + root(lo + k, hi)).digest()

    return root(0, len(level))


def validators_hash(in_order: list[tuple[bytes, int]]) -> bytes:
    return merkle_root([simple_validator(k, p) for k, p in in_order])


def weighted_median(times_and_powers: list[tuple[int, int]]) -> int:
    """BFT time of a commit: the reference's WeightedMedian over the
    (timestamp, voting power) of its votes."""
    median = sum(p for _, p in times_and_powers) // 2
    for when, power in sorted(times_and_powers):
        if median <= power:
            return when
        median -= power
    return 0


def light_lanes(in_order: list[tuple[bytes, int]]) -> int:
    """Signatures VerifyCommitLight checks of a commit every validator
    signed: in order, until the tally passes two thirds of the power."""
    need = 2 * sum(p for _, p in in_order)
    tally = 0
    for i, (_, power) in enumerate(in_order):
        tally += power
        if 3 * tally > need:
            return i + 1
    return len(in_order)


class ValsetModel:
    """The set in force at every height of a chain whose blocks carry
    `val:` txs. `apply_updates=False` is the control: a node that
    never applies a change."""

    def __init__(self, genesis: dict[bytes, int],
                 apply_updates: bool = True):
        self.apply_updates = apply_updates
        genesis = dict(genesis)
        self._at = {1: genesis, 2: genesis}
        self._top = 0    # the last block delivered

    def deliver_block(self, height: int, txs: list[bytes]) -> None:
        """Block `height`'s txs: its updates are in force from
        height + 2 (power 0 removes)."""
        if height != self._top + 1:
            raise ValueError(f"block {height} after {self._top}")
        self._top = height
        updates = [u for u in map(parse_val_tx, txs) if u is not None]
        nxt = self._at[height + 1]
        if updates and self.apply_updates:
            nxt = dict(nxt)
            for key, power in updates:
                if power:
                    nxt[key] = power
                else:
                    nxt.pop(key, None)
        self._at[height + 2] = nxt

    def in_force(self, height: int) -> list[tuple[bytes, int]]:
        """The set that signs block `height`, in order."""
        return ordered(self._at[height])
