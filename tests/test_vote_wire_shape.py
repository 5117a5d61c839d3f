"""A VoteMessage read by the shape of its wire bytes
(consensus/messages.py `_decode_shaped_vote`) against the general
field-by-field decoder behind the same entry, `decode_consensus_msg`:
for every byte string the two give an equal message or raise the same
type, and the bytes alone say which of them answers."""

import itertools
import os

import pytest

from tendermint_tpu.consensus import messages as m
from tendermint_tpu.encoding.proto import Writer, encode_varint
from tendermint_tpu.types.block import BlockID, PartSetHeader
from tendermint_tpu.types.vote import Vote, VoteType

ADDR = bytes(range(100, 120))
SIG = bytes(range(64))
FULL_ID = BlockID(bytes(range(32)), PartSetHeader(3, bytes(range(32, 64))))
ZERO_ID = BlockID(b"", PartSetHeader(0, b""))
BLOCK_IDS = {"full": FULL_ID, "nil": None, "zero": ZERO_ID}
ORIGIN = b"node-7|h=12|r=0|span=99"


def outcome(decode, data):
    try:
        return "ok", decode(data)
    except Exception as e:       # the TYPE is what has to agree
        return "raised", type(e)


def general(data, monkeypatch):
    """What the field-by-field decoder alone makes of `data`."""
    with monkeypatch.context() as mp:
        mp.setattr(m, "_decode_shaped_vote", lambda d: None)
        return outcome(m.decode_consensus_msg, data)


def through_the_entry(data):
    """(outcome, how many the shaped reader took, how many it left)."""
    s0, g0 = m.vote_decode_counts()
    got = outcome(m.decode_consensus_msg, data)
    s1, g1 = m.vote_decode_counts()
    return got, s1 - s0, g1 - g0


def agree(data, monkeypatch, shaped=None):
    """Both paths on `data`; `shaped`: which of them has to have
    answered at the entry (None: the bytes decide, either is right)."""
    want = general(data, monkeypatch)
    got, took, left = through_the_entry(data)
    assert got == want, (data.hex(), got, want)
    assert took + left == (1 if data[:1] == b"\x06" else 0)
    if shaped is not None:
        assert took == (1 if shaped else 0), data.hex()
    if got[0] == "ok" and isinstance(got[1], m.VoteMessage):
        # the message says itself which reader made it
        assert got[1].shaped is bool(took) and want[1].shaped is False
        v, w = got[1].vote, want[1].vote
        assert type(v.type) is type(w.type) is VoteType
        assert type(v.block_id) is type(w.block_id)
    return got


def vote(vtype=VoteType.PRECOMMIT, height=12, round_=0, index=5,
         block_id=FULL_ID, stamp=1_700_000_000_123_456_789, addr=ADDR,
         sig=SIG):
    return Vote(vtype, height, round_, block_id, stamp, addr, index, sig)


def wire(v, origin=None):
    return m.encode_consensus_msg(m.VoteMessage(v, origin))


# -- (a) the grid: whatever the program's encoder writes is shaped ----------

GRID = list(itertools.product(
    (VoteType.PREVOTE, VoteType.PRECOMMIT),
    (1, 127, 128, 2 ** 31, 2 ** 62),
    (0, 1, 300),
    (0, 127, 128, 9_999, 16_384),
    ("full", "nil", "zero"),
    (0, 1_700_000_000),
    (0, 1, 999_999_999),
    (None, ORIGIN),
))
THINNED = GRID[::13]     # 5,400 -> 416, every pair of values kept


def test_the_thinned_grid_keeps_every_pair_of_values():
    pairs = lambda rows: {
        (i, a, j, b) for row in rows
        for (i, a), (j, b) in itertools.combinations(enumerate(row), 2)}
    assert pairs(THINNED) == pairs(GRID)


@pytest.mark.parametrize(
    "vtype,height,round_,index,which,secs,nanos,origin", THINNED)
def test_encoder_output_is_shaped_and_equal(
        vtype, height, round_, index, which, secs, nanos, origin,
        monkeypatch):
    v = vote(vtype, height, round_, index, BLOCK_IDS[which],
             secs * 1_000_000_000 + nanos)
    data = wire(v, origin)
    got = agree(data, monkeypatch, shaped=True)
    assert got == ("ok", m.VoteMessage(v, origin))
    assert m.encode_consensus_msg(got[1]) == data


# -- (b) the bytes decide -----------------------------------------------------

SAMPLE = wire(vote(index=9_999, round_=2), ORIGIN)
OTHERS = {
    "no_origin": wire(vote()),
    "nil": wire(vote(block_id=None)),
    "zero_id": wire(vote(block_id=ZERO_ID), ORIGIN),
    "bare": wire(vote(VoteType.PREVOTE, 1, 0, 0, None, 0)),
}


def whole_without_origin(data: bytes) -> int:
    """The one truncation that leaves a canonical message: the origin
    field cut off whole."""
    return len(data) - 2 - len(ORIGIN) if data.endswith(ORIGIN) else -1


@pytest.mark.parametrize("cut", range(len(SAMPLE)))
def test_every_truncation(cut, monkeypatch):
    agree(SAMPLE[:cut], monkeypatch,
          shaped=cut == whole_without_origin(SAMPLE))


@pytest.mark.parametrize("at", range(len(SAMPLE)))
def test_every_value_of_every_byte(at, monkeypatch):
    for b in range(256):
        if b != SAMPLE[at]:
            agree(SAMPLE[:at] + bytes([b]) + SAMPLE[at + 1:], monkeypatch)


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_truncations_and_flips_of_the_other_layouts(name, monkeypatch):
    data = OTHERS[name]
    agree(data, monkeypatch, shaped=True)
    for cut in range(len(data)):
        agree(data[:cut], monkeypatch,
              shaped=cut == whole_without_origin(data))
    for at in range(len(data)):
        for flip in (0x01, 0x80, 0xFF):
            agree(data[:at] + bytes([data[at] ^ flip]) + data[at + 1:],
                  monkeypatch)


def vote_fields(**over):
    """The sample vote's fields as (tag, bytes-after-the-tag) in wire
    order, for cases that re-assemble them by hand."""
    ts = b"\x08" + encode_varint(1_700_000_000) + b"\x10" + encode_varint(5)
    bid = Writer().bytes(1, FULL_ID.hash).message(
        2, Writer().varint(1, 3).bytes(2, FULL_ID.part_set_header.hash)
    ).finish()
    fields = {
        "type": b"\x08\x02", "height": b"\x10\x0c", "round": b"\x18\x02",
        "block_id": b"\x22" + encode_varint(len(bid)) + bid,
        "timestamp": b"\x2a" + encode_varint(len(ts)) + ts,
        "address": b"\x32\x14" + ADDR, "index": b"\x38\x05",
        "signature": b"\x42\x40" + SIG,
    }
    fields.update(over)
    return fields


def assemble(fields, order=None, vote_len=None, tail=b""):
    body = b"".join(fields[k] for k in (order or fields))
    n = len(body) if vote_len is None else vote_len
    return b"\x06\x0a" + encode_varint(n) + body + tail


def test_the_hand_assembly_is_the_encoders():
    v = vote(round_=2, stamp=1_700_000_000_000_000_005)
    assert assemble(vote_fields()) == wire(v)


def swapped(a, b):
    order = list(vote_fields())
    i, j = order.index(a), order.index(b)
    order[i], order[j] = order[j], order[i]
    return assemble(vote_fields(), order)


def spare(varint: bytes) -> bytes:
    """The same value with one byte more than it needs."""
    return varint[:-1] + bytes([varint[-1] | 0x80]) + b"\x00"


BODY_LEN = len(b"".join(vote_fields().values()))
assert BODY_LEN >= 128      # a length of two bytes, as on the wire
UNKNOWN = b"\x80\x01\x01"     # field 16, a varint: no decoder knows it
LEFT_TO_THE_GENERAL_DECODER = {
    "unknown_field_after_the_vote": assemble(vote_fields(), tail=UNKNOWN),
    "unknown_field_after_the_origin": assemble(
        vote_fields(), tail=b"\x7a\x02hi" + UNKNOWN),
    "unknown_field_inside_the_vote": assemble(
        {**vote_fields(), "x": UNKNOWN}),
    "unknown_field_inside_the_timestamp": assemble(vote_fields(
        timestamp=b"\x2a\x05\x08\x01" + UNKNOWN)),
    "height_and_round_swapped": swapped("height", "round"),
    "address_and_signature_swapped": swapped("address", "signature"),
    "type_last": assemble(vote_fields(), order=[
        k for k in vote_fields() if k != "type"] + ["type"]),
    "height_repeated": assemble({**vote_fields(), "again": b"\x10\x0d"}),
    "signature_repeated": assemble(
        {**vote_fields(), "again": b"\x42\x40" + SIG[::-1]}),
    "vote_repeated": assemble(vote_fields(), tail=assemble(
        vote_fields(height=b"\x10\x0d"))[1:]),
    "origin_repeated": assemble(
        vote_fields(), tail=b"\x7a\x01a\x7a\x01b"),
    "origin_empty": assemble(vote_fields(), tail=b"\x7a\x00"),
    "origin_first": b"\x06\x7a\x01a" + assemble(vote_fields())[1:],
    "no_vote_at_all": b"\x06",
    "only_an_origin": b"\x06\x7a\x01a",
    "height_with_a_spare_byte": assemble(
        vote_fields(height=b"\x10" + spare(b"\x0c"))),
    "index_with_two_spare_bytes": assemble(
        vote_fields(index=b"\x38" + spare(spare(b"\x05")))),
    "vote_length_with_a_spare_byte": b"\x06\x0a" + spare(
        encode_varint(BODY_LEN)) + assemble(vote_fields())[4:],
    "round_written_as_zero": assemble(vote_fields(round=b"\x18\x00")),
    "index_written_as_zero": assemble(vote_fields(index=b"\x38\x00")),
    "height_of_ten_bytes": assemble(vote_fields(
        height=b"\x10" + encode_varint(-5))),
    "height_of_eleven_bytes": assemble(vote_fields(
        height=b"\x10" + b"\xff" * 10 + b"\x01")),
    "seconds_negative": assemble(vote_fields(
        timestamp=b"\x2a\x0b\x08" + encode_varint(-62135596800))),
    "vote_length_one_short": assemble(
        vote_fields(), vote_len=BODY_LEN - 1),
    "vote_length_one_long": assemble(
        vote_fields(), vote_len=BODY_LEN + 1),
    "vote_length_one_long_with_origin": assemble(
        vote_fields(), vote_len=BODY_LEN + 1, tail=b"\x7a\x02hi"),
    "timestamp_length_one_short": assemble(vote_fields(
        timestamp=b"\x2a\x06\x08" + encode_varint(1_700_000_000)
        + b"\x10\x05")),
    "timestamp_length_one_long": assemble(vote_fields(
        timestamp=b"\x2a\x09\x08" + encode_varint(1_700_000_000)
        + b"\x10\x05")),
    "origin_length_one_short": assemble(vote_fields(), tail=b"\x7a\x01hi"),
    "origin_length_one_long": assemble(vote_fields(), tail=b"\x7a\x03hi"),
    "origin_of_two_length_bytes": assemble(
        vote_fields(), tail=b"\x7a\x80\x01" + b"o" * 128),
    "block_id_length_one_short": assemble(vote_fields(
        block_id=b"\x22\x47" + vote_fields()["block_id"][2:])),
    "block_id_length_one_long": assemble(vote_fields(
        block_id=b"\x22\x49" + vote_fields()["block_id"][2:])),
    "block_id_of_a_long_hash": assemble(vote_fields(
        block_id=b"\x22\x23\x0a\x21" + bytes(33))),
    "block_id_of_128_parts": wire(vote(block_id=BlockID(
        FULL_ID.hash, PartSetHeader(128, FULL_ID.part_set_header.hash)))),
    "block_id_hash_alone": wire(vote(block_id=BlockID(FULL_ID.hash, None))),
    "type_zero": assemble(vote_fields(type=b"\x08\x00")),
    "type_three": assemble(vote_fields(type=b"\x08\x03")),
    "type_absent": assemble(
        {k: v for k, v in vote_fields().items() if k != "type"}),
    "address_of_19": assemble(vote_fields(address=b"\x32\x13" + ADDR[:19])),
    "address_of_21": assemble(
        vote_fields(address=b"\x32\x15" + ADDR + b"\x00")),
    "address_absent": assemble(
        {k: v for k, v in vote_fields().items() if k != "address"}),
    "signature_of_63": assemble(
        vote_fields(signature=b"\x42\x3f" + SIG[:63])),
    "signature_of_65": assemble(
        vote_fields(signature=b"\x42\x41" + SIG + b"\x00")),
    "signature_absent": assemble(
        {k: v for k, v in vote_fields().items() if k != "signature"}),
    "another_wire_type_for_height": assemble(
        vote_fields(height=b"\x11" + bytes(8))),
    "a_group_wire_type": assemble({**vote_fields(), "x": b"\x83\x01"}),
}


@pytest.mark.parametrize("name", sorted(LEFT_TO_THE_GENERAL_DECODER))
def test_what_misses_the_layout_is_the_general_decoders(name, monkeypatch):
    agree(LEFT_TO_THE_GENERAL_DECODER[name], monkeypatch, shaped=False)


STILL_SHAPED = {
    "timestamp_present_and_empty": assemble(
        vote_fields(timestamp=b"\x2a\x00")),
    "seconds_alone": assemble(vote_fields(
        timestamp=b"\x2a\x06\x08" + encode_varint(1_700_000_000))),
    "nanos_alone": assemble(vote_fields(timestamp=b"\x2a\x02\x10\x05")),
    "nanos_past_a_second": assemble(vote_fields(
        timestamp=b"\x2a\x06\x10" + encode_varint(4_000_000_000))),
    "height_absent": assemble(
        {k: v for k, v in vote_fields().items() if k != "height"}),
    "height_of_nine_bytes": assemble(vote_fields(
        height=b"\x10" + encode_varint(2 ** 63 - 1))),
    "block_id_of_72_bytes_that_are_no_fields_we_know": assemble(vote_fields(
        block_id=b"\x22\x48" + (b"\x98\x01\x07" * 24))),
    "origin_of_127_bytes": assemble(
        vote_fields(), tail=b"\x7a\x7f" + b"o" * 127),
}


@pytest.mark.parametrize("name", sorted(STILL_SHAPED))
def test_what_keeps_the_layout_is_shaped(name, monkeypatch):
    got = agree(STILL_SHAPED[name], monkeypatch, shaped=True)
    assert got[0] == "ok"


def test_a_block_id_the_general_reader_refuses_is_refused_alike(monkeypatch):
    # 72 bytes that end inside a length-delimited field
    data = assemble(vote_fields(
        block_id=b"\x22\x48" + bytes(70) + b"\x0a\x7f"))
    got = agree(data, monkeypatch, shaped=False)
    assert got == ("raised", ValueError)


def test_other_messages_are_not_counted_as_votes():
    data = m.encode_consensus_msg(m.HasVoteMessage(5, 0, 1, 3))
    got, took, left = through_the_entry(data)
    assert got == ("ok", m.HasVoteMessage(5, 0, 1, 3))
    assert (took, left) == (0, 0)
    assert through_the_entry(b"")[0] == ("raised", ValueError)


# -- (c) the BlockID intern map ----------------------------------------------


def test_votes_of_one_block_share_one_block_id_and_no_vote():
    msgs = [wire(vote(index=i, addr=os.urandom(20), sig=os.urandom(64)))
            for i in range(10_000)]
    s0, g0 = m.vote_decode_counts()
    votes = [m.decode_consensus_msg(d).vote for d in msgs]
    assert m.vote_decode_counts() == (s0 + 10_000, g0)
    assert {id(v.block_id) for v in votes} == {id(votes[0].block_id)}
    assert votes[0].block_id == FULL_ID
    assert len({id(v) for v in votes}) == 10_000
    assert [v.validator_index for v in votes] == list(range(10_000))


def test_invented_block_ids_leave_the_map_at_its_bound():
    first = wire(vote(block_id=BlockID(b"\x01" * 32, PartSetHeader(
        1, b"\x02" * 32))))
    kept = m.decode_consensus_msg(first).vote.block_id
    for i in range(1_000):
        bid = BlockID(i.to_bytes(32, "big"), PartSetHeader(
            1 + i % 100, os.urandom(32)))
        got = m.decode_consensus_msg(wire(vote(block_id=bid))).vote
        assert got.block_id == bid
        assert len(m._BLOCK_IDS) <= m._BLOCK_IDS_MAX
    assert len(m._BLOCK_IDS) == m._BLOCK_IDS_MAX
    # the oldest went out: the first block's votes get a new, equal one
    again = m.decode_consensus_msg(first).vote.block_id
    assert again == kept and again is not kept
    # and the newest stayed
    assert m.decode_consensus_msg(first).vote.block_id is again
