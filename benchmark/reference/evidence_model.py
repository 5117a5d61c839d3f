"""Plain reference: DuplicateVoteEvidence as the reference node checks
it, one evidence and one signature at a time, and the set of evidence a
chain has committed. It imports nothing of the program.

From Tendermint Core v0.34:

  evidence/pool.go:181 CheckEvidence   every evidence of a proposed
      block, in list order: already in this block -> refused; already
      committed -> refused; else verify (a node that syncs holds no
      pending evidence, so none is skipped).
  evidence/verify.go:25 verify         the block time and the validator
      set of the evidence's height; expired only when BOTH its age in
      blocks and its age in time pass the consensus params'.
  evidence/verify.go:165 VerifyDuplicateVote   same height, round and
      type; same validator; different block ids, in canonical order;
      the validator in the set of that height; the recorded validator
      power, total power and time those of the chain; then vote A's
      signature, then vote B's, each under the validator's own key and
      key type (ZIP-215 ed25519 / schnorrkel sr25519).
  types/evidence.go ValidateBasic      both votes present and
      well-formed (here: a signature of 64 bytes on each).

A vote's sign bytes are the canonical vote (reference/canonical.py
states the encoding; that file fixes the type to precommit, so the
same encoding is written here with the type free).

`check_block(..., lanes=...)` verifies the signatures of the named
lanes only: 10,000 pure-Python verifications take a minute, and the
comparison samples (benchmark/README-mixed.md). `weak` names a control:
a reference that checks less, which `correct` must tell from the real
one.
"""

from __future__ import annotations

import hashlib
import struct
from typing import NamedTuple

from benchmark.reference import ed25519_zip215 as ed_ref
from benchmark.reference import sr25519_schnorrkel as sr_ref
from benchmark.reference.canonical import varint

PREVOTE, PRECOMMIT = 1, 2


class BlockId(NamedTuple):
    hash: bytes
    parts_total: int
    parts_hash: bytes

    def key(self) -> bytes:
        """The order DuplicateVoteEvidence puts its votes in
        (types/evidence.go NewDuplicateVoteEvidence: by BlockID.Key())."""
        return self.hash + struct.pack(">I", self.parts_total) \
            + self.parts_hash


class Vote(NamedTuple):
    type: int
    height: int
    round: int
    block_id: BlockId
    timestamp: int          # ns
    validator: bytes        # address
    signature: bytes


class DuplicateVote(NamedTuple):
    vote_a: Vote
    vote_b: Vote
    total_voting_power: int
    validator_power: int
    timestamp: int          # the block time of the votes' height

    def ident(self) -> bytes:
        """What tells one evidence from another: everything in it."""
        h = hashlib.sha256()
        for v in (self.vote_a, self.vote_b):
            h.update(repr(tuple(v)).encode())
        h.update(repr(tuple(self[2:])).encode())
        return h.digest()


def _fv(field: int, v: int) -> bytes:
    return b"" if v == 0 else varint(field << 3) + varint(v)


def _fb(field: int, b: bytes) -> bytes:
    return varint((field << 3) | 2) + varint(len(b)) + b


def vote_sign_bytes(chain_id: str, vote_type: int, height: int,
                    round_: int, block_id: BlockId, time_ns: int) -> bytes:
    """CanonicalVote, length-delimited (types/canonical.go)."""
    body = _fv(1, vote_type)
    body += varint((2 << 3) | 1) + struct.pack("<q", height)
    if round_:
        body += varint((3 << 3) | 1) + struct.pack("<q", round_)
    psh = _fv(1, block_id.parts_total) + _fb(2, block_id.parts_hash)
    body += _fb(4, _fb(1, block_id.hash) + _fb(2, psh))
    if time_ns:
        body += _fb(5, _fv(1, time_ns // 1_000_000_000)
                    + _fv(2, time_ns % 1_000_000_000))
    body += _fb(6, chain_id.encode())
    return varint(len(body)) + body


def verify_signature(kind: str, pub_key: bytes, msg: bytes,
                     sig: bytes) -> bool:
    if kind == "ed25519":
        return ed_ref.verify(pub_key, msg, sig)
    if kind == "sr25519":
        return sr_ref.verify(pub_key, msg, sig)
    raise ValueError(f"no reference verifier for key type {kind!r}")


class EvidenceModel:
    """The chain as evidence checking reads it, and what it committed.

    `validators`: {height: {address: (key type, public key, power)}};
    `block_times`: {height: ns}; `max_age`: (blocks, ns)."""

    def __init__(self, chain_id: str, validators: dict, block_times: dict,
                 max_age: tuple[int, int]):
        self.chain_id = chain_id
        self.validators = validators
        self.block_times = block_times
        self.max_age = max_age
        self.committed: set[bytes] = set()

    def check_block(self, evidence: list[DuplicateVote], state_height: int,
                    state_time: int, lanes=None,
                    weak: str | None = None):
        """CheckEvidence for the list of a proposed block, on a chain
        at `state_height` / `state_time`: None, or (index, reason) of
        the first evidence refused. `lanes`: the (index, "A" | "B")
        whose signatures are verified (None: all)."""
        seen = set()
        for i, ev in enumerate(evidence):
            ident = ev.ident()
            if ident in seen:
                return i, "duplicate evidence in block"
            seen.add(ident)
            if ident in self.committed:
                return i, "evidence was already committed"
            why = self._verify(i, ev, state_height, state_time, lanes, weak)
            if why is not None:
                return i, why
        return None

    def commit_block(self, evidence: list[DuplicateVote]) -> None:
        self.committed.update(ev.ident() for ev in evidence)

    def _verify(self, i, ev, state_height, state_time, lanes, weak):
        a, b = ev.vote_a, ev.vote_b
        if len(a.signature) != 64 or len(b.signature) != 64:
            return "malformed vote"
        if not a.block_id.key() < b.block_id.key():
            return "duplicate votes in wrong order or identical"
        height = a.height
        when = self.block_times.get(height)
        if when is None:
            return f"no committed block at evidence height {height}"
        if state_height - height > self.max_age[0] and \
                state_time - when > self.max_age[1]:
            return f"evidence from height {height} is too old"
        if (a.height, a.round, a.type) != (b.height, b.round, b.type):
            return "votes are from different H/R/S"
        if a.validator != b.validator:
            return "votes are from different validators"
        vals = self.validators[height]
        if a.validator not in vals:
            return "validator not in set"
        kind, pub_key, power = vals[a.validator]
        if ev.validator_power != power:
            return "validator power mismatch"
        if ev.total_voting_power != sum(p for _, _, p in vals.values()):
            return "total voting power mismatch"
        if ev.timestamp != when:
            return "evidence time != block time"
        if weak == "skips_evidence_signatures":
            return None
        for which, vote in (("A", a), ("B", b)):
            if weak == "evidence_first_vote_only" and which == "B":
                break
            if lanes is not None and (i, which) not in lanes:
                continue
            msg = vote_sign_bytes(self.chain_id, vote.type, vote.height,
                                  vote.round, vote.block_id,
                                  vote.timestamp)
            if not verify_signature(kind, pub_key, msg, vote.signature):
                return f"invalid signature on vote {which}"
        return None
