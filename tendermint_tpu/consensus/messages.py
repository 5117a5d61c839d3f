"""Consensus messages (reference: consensus/msgs.go proto codec).

Wire form: a one-byte type tag + a payload. Votes/proposals ride their
canonical proto encodings (types/vote.py, types/proposal.py); block
parts carry their merkle proof inline. The same codec serves the WAL
and, later, the consensus reactor channels."""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field

from ..types.block import BlockID, Part, block_id_writer, read_block_id
from ..encoding.proto import Reader, Writer
from ..libs.bits import BitArray
from ..types.proposal import Proposal
from ..types.vote import Vote, VoteType


@dataclass
class NewRoundStepMessage:
    height: int
    round: int
    step: int
    seconds_since_start_time: int = 0
    last_commit_round: int = 0


@dataclass
class NewValidBlockMessage:
    height: int
    round: int
    block_parts_header: object  # PartSetHeader
    block_parts: BitArray
    is_commit: bool


@dataclass
class ProposalMessage:
    proposal: Proposal
    # Optional cross-node trace context (libs/tracing.py origin tag):
    # opaque on the wire, skipped by decoders that predate it. Rides
    # the three block-lifecycle messages only (Proposal/BlockPart/Vote).
    origin: bytes | None = None


@dataclass
class ProposalPOLMessage:
    height: int
    proposal_pol_round: int
    proposal_pol: BitArray


@dataclass
class BlockPartMessage:
    height: int
    round: int
    part: Part
    origin: bytes | None = None


@dataclass
class VoteMessage:
    vote: Vote
    origin: bytes | None = None
    # how it was decoded, no part of what it says: True where
    # decode_consensus_msg read the wire bytes by their shape
    shaped: bool = field(default=False, compare=False, repr=False)


@dataclass
class HasVoteMessage:
    height: int
    round: int
    type: int
    index: int


@dataclass
class VoteSetMaj23Message:
    height: int
    round: int
    type: int
    block_id: BlockID


@dataclass
class VoteSetBitsMessage:
    height: int
    round: int
    type: int
    block_id: BlockID
    votes: BitArray


# --- wire codec --------------------------------------------------------------

_TAG = {
    NewRoundStepMessage: 1,
    NewValidBlockMessage: 2,
    ProposalMessage: 3,
    ProposalPOLMessage: 4,
    BlockPartMessage: 5,
    VoteMessage: 6,
    HasVoteMessage: 7,
    VoteSetMaj23Message: 8,
    VoteSetBitsMessage: 9,
}
_BY_TAG = {v: k for k, v in _TAG.items()}


def _bits_writer(b: BitArray) -> Writer:
    w = Writer()
    w.varint(1, b.size)
    w.bytes(2, b.to_bytes())
    return w


def _read_bits(data: bytes) -> BitArray:
    r = Reader(data)
    size, raw = 0, b""
    while not r.at_end():
        f, wt = r.field()
        if f == 1:
            size = r.varint()
        elif f == 2:
            raw = r.bytes()
        else:
            r.skip(wt)
    return BitArray.from_bytes(size, raw)


def _part_writer(p: Part) -> Writer:
    return p.to_proto()


def _read_part(data: bytes) -> Part:
    return Part.from_bytes(data)


def encode_consensus_msg(msg) -> bytes:
    tag = _TAG[type(msg)]
    w = Writer()
    if isinstance(msg, NewRoundStepMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        w.varint(3, msg.step)
        w.varint(4, msg.seconds_since_start_time)
        w.varint(5, msg.last_commit_round)
    elif isinstance(msg, NewValidBlockMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        ph = Writer()
        ph.varint(1, msg.block_parts_header.total)
        ph.bytes(2, msg.block_parts_header.hash)
        w.message(3, ph)
        w.message(4, _bits_writer(msg.block_parts))
        w.bool(5, msg.is_commit)
    elif isinstance(msg, ProposalMessage):
        w.message(1, msg.proposal.to_proto())
        if msg.origin:
            w.bytes(15, msg.origin)
    elif isinstance(msg, ProposalPOLMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.proposal_pol_round, skip_zero=False)
        w.message(3, _bits_writer(msg.proposal_pol))
    elif isinstance(msg, BlockPartMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        w.message(3, _part_writer(msg.part))
        if msg.origin:
            w.bytes(15, msg.origin)
    elif isinstance(msg, VoteMessage):
        w.message(1, msg.vote.to_proto())
        if msg.origin:
            w.bytes(15, msg.origin)
    elif isinstance(msg, HasVoteMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        w.varint(3, msg.type)
        w.varint(4, msg.index, skip_zero=False)
    elif isinstance(msg, VoteSetMaj23Message):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        w.varint(3, msg.type)
        w.message(4, block_id_writer(msg.block_id))
    elif isinstance(msg, VoteSetBitsMessage):
        w.varint(1, msg.height)
        w.varint(2, msg.round, skip_zero=False)
        w.varint(3, msg.type)
        w.message(4, block_id_writer(msg.block_id))
        w.message(5, _bits_writer(msg.votes))
    return bytes([tag]) + w.finish()


# A VoteMessage read by its SHAPE. A height of a 10,000-validator chain
# hands the reactor ~26,000 of them, and every encoder that writes
# canonical proto3 (this module's, a Go peer's marshaller) lays one out
# the same way: fields in ascending order, a zero left out, every
# varint minimal. One compiled pattern over that layout takes the
# fields apart in a single C-level pass,
#
#   06 0a len | 08 type | [10 height] | [18 round] | [22 48 block_id[72]
#   | 22 02 12 00] | [2a len [08 secs] [10 nanos]] | 32 14 addr[20]
#   | [38 index] | 42 40 sig[64] | [7a len origin]
#
# and the bytes ALONE pick the path: whatever the pattern does not
# match exactly (an unknown or repeated field, another order, a varint
# with a spare byte or of ten, a type outside {1, 2}, an address not of
# 20 bytes, a signature not of 64, a block_id of another size, a length
# that disagrees with what it covers) goes to the general decoder
# whole, which accepts or raises as it always did. The varints the
# pattern takes are 1-9 bytes with a last byte that is not zero: under
# 2^63, so no sign and no overflow is left to check.
_UV = rb"[\x80-\xff]{0,8}[\x01-\x7f]"
_VOTE_SHAPE = re.compile(
    rb"\x06\x0a([\x01-\x7f]|[\x80-\xff][\x01-\x7f])"
    rb"\x08([\x01\x02])"
    rb"(?:\x10(" + _UV + rb"))?"
    rb"(?:\x18(" + _UV + rb"))?"
    rb"(?:\x22(\x48.{72}|\x02\x12\x00))?"
    rb"(?:\x2a([\x00-\x14])((?:\x08(" + _UV + rb"))?(?:\x10(" + _UV + rb"))?))?"
    rb"\x32\x14(.{20})"
    rb"(?:\x38(" + _UV + rb"))?"
    rb"\x42\x40(.{64})"
    rb"(?:\x7a([\x01-\x7f])(.+))?",
    re.DOTALL)
_VOTE_TYPE = {bytes([t]): t for t in VoteType}

# The 20,000 votes of a height name one block (and nil): their BlockID
# comes from this map, keyed by the sub-message's bytes (behind their
# length byte) and built by the general reader on a miss. BlockID and
# PartSetHeader are frozen, so one object serves every vote; the bound
# (oldest out) is what a peer that invents block IDs can make it hold.
# Votes are never shared.
_BLOCK_IDS: dict[bytes, BlockID] = {}
_BLOCK_IDS_MAX = 8
_block_ids_lock = threading.Lock()

# VoteMessages decoded by their shape / handed to the general decoder:
# a process's running count for whoever asks (the tests; a restart's
# WAL catch-up, whose messages nobody else sees). No path reads it: a
# message says how it was decoded itself (VoteMessage.shaped).
_vote_decodes = [0, 0]


def vote_decode_counts() -> tuple[int, int]:
    """(shaped, general): how many VoteMessages this process decoded
    by their layout, and how many it left to the general decoder
    (which may have refused them)."""
    return _vote_decodes[0], _vote_decodes[1]


def _uvarint(b: bytes) -> int:
    """A varint the pattern took: minimal, under 2^63."""
    if len(b) == 1:
        return b[0]
    if len(b) == 2:     # a vote's length, an index past 127
        return b[0] & 0x7F | b[1] << 7
    v = 0
    for c in reversed(b):
        v = v << 7 | c & 0x7F
    return v


def _interned_block_id(raw: bytes) -> BlockID | None:
    """The map's miss: the general reader's BlockID for the sub-message
    behind `raw`'s length byte (None where it refuses the bytes: the
    general decoder then does too)."""
    try:
        bid = read_block_id(raw[1:])
    except ValueError:
        return None
    with _block_ids_lock:
        if len(_BLOCK_IDS) >= _BLOCK_IDS_MAX:
            del _BLOCK_IDS[next(iter(_BLOCK_IDS))]
        _BLOCK_IDS[raw] = bid
    return bid


def _decode_shaped_vote(data: bytes) -> "VoteMessage | None":
    """`data` as a VoteMessage if it has the canonical layout, else
    None (and the general decoder says what it is)."""
    mt = _VOTE_SHAPE.fullmatch(data)
    if mt is None:
        return None
    (ln, vtype, height, round_, raw_id, ts_len, ts, secs, nanos,
     addr, index, sig, origin_len, origin) = mt.groups()
    # the two outer lengths, against the bytes they cover
    covered = len(data) - 2 - len(ln)
    if origin is not None:
        if origin_len[0] != len(origin):
            return None
        covered -= 2 + len(origin)
    if _uvarint(ln) != covered:
        return None
    if ts_len is None:
        stamp = 0
    elif ts_len[0] != len(ts):
        return None
    else:
        stamp = (_uvarint(secs) * 1_000_000_000 if secs else 0) + (
            _uvarint(nanos) if nanos else 0)
    if raw_id is None:
        block_id = None
    else:
        block_id = _BLOCK_IDS.get(raw_id)
        if block_id is None:
            block_id = _interned_block_id(raw_id)
            if block_id is None:
                return None
    return VoteMessage(Vote(
        _VOTE_TYPE[vtype], _uvarint(height) if height else 0,
        _uvarint(round_) if round_ else 0, block_id, stamp, addr,
        _uvarint(index) if index else 0, sig), origin, True)


def decode_consensus_msg(data: bytes):
    if not data:
        raise ValueError("empty consensus message")
    if data[0] == 6:
        msg = _decode_shaped_vote(data)
        if msg is not None:
            _vote_decodes[0] += 1
            return msg
        _vote_decodes[1] += 1
    cls = _BY_TAG.get(data[0])
    if cls is None:
        raise ValueError(f"unknown consensus message tag {data[0]}")
    r = Reader(data[1:])
    if cls is NewRoundStepMessage:
        kw = dict(height=0, round=0, step=0, seconds_since_start_time=0,
                  last_commit_round=0)
        names = {1: "height", 2: "round", 3: "step",
                 4: "seconds_since_start_time", 5: "last_commit_round"}
        while not r.at_end():
            f, wt = r.field()
            if f in names:
                kw[names[f]] = r.varint()
            else:
                r.skip(wt)
        return cls(**kw)
    if cls is NewValidBlockMessage:
        from ..types.block import PartSetHeader

        height = round_ = 0
        psh = PartSetHeader(0, b"")
        bits = BitArray(0)
        is_commit = False
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                height = r.varint()
            elif f == 2:
                round_ = r.varint()
            elif f == 3:
                rr = Reader(r.bytes())
                total, h = 0, b""
                while not rr.at_end():
                    ff, wwt = rr.field()
                    if ff == 1:
                        total = rr.varint()
                    elif ff == 2:
                        h = rr.bytes()
                    else:
                        rr.skip(wwt)
                psh = PartSetHeader(total, h)
            elif f == 4:
                bits = _read_bits(r.bytes())
            elif f == 5:
                is_commit = bool(r.varint())
            else:
                r.skip(wt)
        return cls(height, round_, psh, bits, is_commit)
    if cls is ProposalMessage:
        prop = None
        origin = None
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                prop = Proposal.from_bytes(r.bytes())
            elif f == 15:
                origin = r.bytes()
            else:
                r.skip(wt)
        if prop is None:
            raise ValueError("ProposalMessage without a proposal")
        return cls(prop, origin=origin)
    if cls is ProposalPOLMessage:
        height = pol_round = 0
        bits = BitArray(0)
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                height = r.varint()
            elif f == 2:
                pol_round = r.varint()
            elif f == 3:
                bits = _read_bits(r.bytes())
            else:
                r.skip(wt)
        return cls(height, pol_round, bits)
    if cls is BlockPartMessage:
        height = round_ = 0
        part = None
        origin = None
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                height = r.varint()
            elif f == 2:
                round_ = r.varint()
            elif f == 3:
                part = _read_part(r.bytes())
            elif f == 15:
                origin = r.bytes()
            else:
                r.skip(wt)
        if part is None:
            raise ValueError("BlockPartMessage without a part")
        return cls(height, round_, part, origin=origin)
    if cls is VoteMessage:
        vote = None
        origin = None
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                vote = Vote.from_bytes(r.bytes())
            elif f == 15:
                origin = r.bytes()
            else:
                r.skip(wt)
        if vote is None:
            raise ValueError("VoteMessage without a vote")
        return cls(vote, origin=origin)
    if cls is HasVoteMessage:
        kw = dict(height=0, round=0, type=0, index=0)
        names = {1: "height", 2: "round", 3: "type", 4: "index"}
        while not r.at_end():
            f, wt = r.field()
            if f in names:
                kw[names[f]] = r.varint()
            else:
                r.skip(wt)
        return cls(**kw)
    if cls is VoteSetMaj23Message:
        height = round_ = type_ = 0
        bid = BlockID(b"", None)
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                height = r.varint()
            elif f == 2:
                round_ = r.varint()
            elif f == 3:
                type_ = r.varint()
            elif f == 4:
                bid = read_block_id(r.bytes())
            else:
                r.skip(wt)
        return cls(height, round_, type_, bid)
    if cls is VoteSetBitsMessage:
        height = round_ = type_ = 0
        bid = BlockID(b"", None)
        bits = BitArray(0)
        while not r.at_end():
            f, wt = r.field()
            if f == 1:
                height = r.varint()
            elif f == 2:
                round_ = r.varint()
            elif f == 3:
                type_ = r.varint()
            elif f == 4:
                bid = read_block_id(r.bytes())
            elif f == 5:
                bits = _read_bits(r.bytes())
            else:
                r.skip(wt)
        return cls(height, round_, type_, bid, bits)
    raise AssertionError("unreachable")
