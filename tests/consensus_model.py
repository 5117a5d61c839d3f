"""Plain reference: a round-0 follower of Tendermint consensus, written
from the reference's `types/vote_set.go` (`AddVote`: verify the
signature, then tally by power), `consensus/state.go` (`addVote`: the
polka and the commit at MORE than 2/3 of the power) and
`types/canonical.go`. It imports nothing of the program: the copies
beside it give the canonical sign bytes, the ZIP-215 verifier and the
kvstore application.

A height is followed from what the validators' peers handed over, in
the order they did: each vote is verified ONE AT A TIME and only then
tallied, a second vote of a validator changes nothing, and the height
ends with the block ID that more than 2/3 of the power precommitted,
the app hash after the block's txs and the validator indexes whose
prevote and precommit a follower holds when everything handed over has
been taken. A follower that moves on at 2/3 holds a subset of those
that still carries more than 2/3: that is what the comparison allows.

`verify` defaults to the copied ZIP-215 verifier. A caller with tens of
thousands of votes a height hands in one that runs the copy on a seeded
sample and trusts the generator's own record for the rest, and says so
(the benchmark's `check()`).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref
from benchmark.reference.kvstore_model import KVStoreModel

PREVOTE, PRECOMMIT = 1, 2


def vote_sign_bytes(chain_id: str, vote_type: int, height: int,
                    block_hash: bytes, parts_total: int,
                    parts_hash: bytes, time_ns: int) -> bytes:
    """CanonicalVote of a round-0 vote for a block. `canonical.py`
    writes the precommit's; the type is the first field (one varint
    byte after its tag) and the only difference."""
    pre, suf = canonical.vote_sign_parts(
        chain_id, height, 0, block_hash, parts_total, parts_hash)
    if pre[:2] != bytes([0x08, canonical.PRECOMMIT]):
        raise AssertionError("canonical.py no longer starts a vote "
                             "with its type")
    return canonical.with_timestamp(bytes([0x08, vote_type]) + pre[2:],
                                    suf, time_ns)


@dataclass
class HeightOutcome:
    height: int
    block_hash: bytes | None      # what > 2/3 precommitted, else None
    polka: bool
    app_hash: bytes
    members: dict = field(default_factory=dict)   # type -> set of indexes
    refused: list = field(default_factory=list)   # (type, index, signature)


class Follower:
    """`validators` is the set in its order: (public key, power)."""

    def __init__(self, chain_id: str, validators: list[tuple[bytes, int]],
                 verify=ref.verify):
        self.chain_id = chain_id
        self.validators = validators
        self.total = sum(p for _, p in validators)
        self.verify = verify
        self.app = KVStoreModel()

    def follow(self, height: int, block_hash: bytes, parts_total: int,
               parts_hash: bytes, txs: list[bytes], votes) -> HeightOutcome:
        """`votes`: (type, validator index, time_ns, signature) in the
        order handed over, all for this block in round 0."""
        members = {PREVOTE: set(), PRECOMMIT: set()}
        power = {PREVOTE: 0, PRECOMMIT: 0}
        refused = []
        for vtype, index, time_ns, sig in votes:
            if index in members[vtype]:
                continue
            pub, weight = self.validators[index]
            msg = vote_sign_bytes(self.chain_id, vtype, height, block_hash,
                                  parts_total, parts_hash, time_ns)
            if not self.verify(pub, msg, sig):
                refused.append((vtype, index, sig))
                continue
            members[vtype].add(index)
            power[vtype] += weight
        committed = 3 * power[PRECOMMIT] > 2 * self.total
        if committed:
            for tx in txs:
                self.app.deliver(tx)
        return HeightOutcome(
            height=height,
            block_hash=block_hash if committed else None,
            polka=3 * power[PREVOTE] > 2 * self.total,
            app_hash=self.app.app_hash(), members=members, refused=refused)

    def holds_two_thirds(self, indexes) -> bool:
        return 3 * sum(self.validators[i][1] for i in indexes) \
            > 2 * self.total
