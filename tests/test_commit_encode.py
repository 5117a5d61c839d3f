"""A Commit's wire bytes from its columns (types/sign_batch.py
commit_sig_rows) must equal the per-slot writer's, always.

CommitSig.to_proto() is the definition. Commit.to_proto() and
Commit.hash() take the slots' rows from an array encoder; block
hashes, part-set headers, the block store's rows and every
SignedHeader on the wire rest on the two being BYTE-IDENTICAL, and on
a commit with a slot that fits no column reaching the per-slot writer.
The digests below were taken from the tree BEFORE the array encoder
(commit 277ba1b), with this file's own builders."""

import hashlib
import random

import pytest

from tendermint_tpu.crypto import ed25519, merkle, sr25519
from tendermint_tpu.encoding.proto import Writer
from tendermint_tpu.types import canonical
from tendermint_tpu.types.block import (
    Block, BlockID, BlockIDFlag, Commit, CommitSig, Data, Header,
    PartSetHeader, block_id_writer,
)
from tendermint_tpu.types.evidence import EvidenceData
from tendermint_tpu.types.sign_batch import (
    columnar_encodes, commit_sig_rows,
)
from tendermint_tpu.types.vote import VoteType

CHAIN_ID = "encode-chain"
BID = BlockID(bytes(range(32)), PartSetHeader(3, bytes(range(32, 64))))
S = 1_000_000_000

# nanos of every varint length, 0 (no field) among them
NANOS = [0, 1, 127, 128, 16_383, 16_384, (1 << 21) - 1, 1 << 21,
         (1 << 28) - 1, 1 << 28, S - 1]
# seconds of every varint length that nanoseconds in an int64 reach: 0
# (no field), both sides of 2^28 (four bytes | five), today's, the last
# (2^35 seconds, where a sixth byte begins, is past 2^63 ns: such a
# slot fits no column, below)
SECS = [0, 1, 127, 128, 16_383, 16_384, (1 << 21) - 1, 1 << 21,
        (1 << 28) - 1, 1 << 28, 1_753_928_000, (1 << 63) // S - 1]


def per_slot_rows(commit) -> bytes:
    """What Commit.to_proto() wrote for the slots before the columns."""
    w = Writer()
    for cs in commit.signatures:
        w.message(4, cs.to_proto())
    return w.finish()


def per_slot_bytes(commit) -> bytes:
    w = Writer()
    w.varint(1, commit.height)
    w.varint(2, commit.round)
    w.message(3, block_id_writer(commit.block_id))
    return w.finish() + per_slot_rows(commit)


def per_slot_hash(commit) -> bytes:
    return merkle.hash_from_byte_slices(
        [cs.to_proto().finish() for cs in commit.signatures])


def random_slot(rng, odd_flags: bool) -> CommitSig:
    kind = rng.random()
    if kind < 0.15:
        return CommitSig.absent()
    if kind < 0.30:
        flag = BlockIDFlag.NIL
    elif kind < 0.35 and odd_flags:
        # no BlockIDFlag, but a byte: it fits the column (0 writes no
        # field, 128 and up two varint bytes)
        flag = rng.choice([0, 4, 127, 128, 255])
    else:
        flag = BlockIDFlag.COMMIT
    pick = rng.random()
    if pick < 0.1:
        ts = 0
    elif pick < 0.5:
        ts = rng.choice(SECS) * S + rng.choice(NANOS)
    else:
        ts = 1_753_928_000 * S + rng.randrange(2 * S)
    return CommitSig(flag, rng.randbytes(20), ts, rng.randbytes(64))


def random_commit(n: int, seed: int, odd_flags: bool = False) -> Commit:
    rng = random.Random(seed)
    return Commit(rng.randrange(1, 1 << 40), rng.randrange(3), BID,
                  [random_slot(rng, odd_flags) for _ in range(n)])


@pytest.mark.parametrize("n", [0, 1, 4, 667, 1_000, 10_000])
def test_rows_equal_the_per_slot_writer(n):
    for seed in range(20 if n <= 4 else 2):
        commit = random_commit(n, 3800 + seed, odd_flags=seed % 2 == 1)
        before = columnar_encodes()
        rows = commit_sig_rows(commit)
        assert columnar_encodes() == before + 1
        assert rows.wire == per_slot_rows(commit)
        assert rows.leaves() == [cs.to_proto().finish()
                                 for cs in commit.signatures]
        assert commit.to_bytes() == per_slot_bytes(commit)
        assert commit.hash() == per_slot_hash(commit)
        back = Commit.from_bytes(commit.to_bytes())
        # a flag of 0 writes no field and reads back as the decoder's
        # default, as it always did
        for cs in commit.signatures:
            cs.block_id_flag = cs.block_id_flag or BlockIDFlag.ABSENT
        assert back == commit


def test_every_timestamp_shape_in_one_commit():
    """Every seconds width against every nanos width, side by side in
    one commit: a piece's columns hold the widest and the mask cuts
    each row to its own."""
    rng = random.Random(38)
    sigs = [CommitSig(BlockIDFlag.COMMIT, rng.randbytes(20),
                      secs * S + nanos, rng.randbytes(64))
            for secs in SECS for nanos in NANOS]
    commit = Commit(7, 1, BID, sigs)
    assert commit_sig_rows(commit).wire == per_slot_rows(commit)
    assert commit.to_bytes() == per_slot_bytes(commit)


def _odd(**change):
    def apply(cs):
        for key, value in change.items():
            setattr(cs, key, value)
    return apply


def _absent_with(**change):
    def apply(cs):
        cs.block_id_flag = BlockIDFlag.ABSENT
        cs.validator_address = cs.signature = b""
        cs.timestamp = 0
        _odd(**change)(cs)
    return apply


FALLS_BACK = {
    "flag_300": _odd(block_id_flag=300),
    "flag_negative": _odd(block_id_flag=-1),
    "timestamp_2_63": _odd(timestamp=1 << 63),
    "timestamp_minus_1": _odd(timestamp=-1),
    "seconds_below_2_35": _odd(timestamp=((1 << 35) - 1) * S + 1),
    "seconds_2_35": _odd(timestamp=(1 << 35) * S),
    "address_19": _odd(validator_address=b"\x07" * 19),
    "address_32": _odd(validator_address=b"\x07" * 32),
    "address_empty_present": _odd(validator_address=b""),
    "signature_65": _odd(signature=b"\x09" * 65),
    "signature_empty_present": _odd(signature=b""),
    "absent_with_address": _absent_with(validator_address=b"\x07" * 20),
    "absent_with_signature": _absent_with(signature=b"\x09" * 64),
    "absent_with_timestamp": _absent_with(timestamp=5),
}


@pytest.mark.parametrize("kind", sorted(FALLS_BACK))
@pytest.mark.parametrize("n, slot", [(1, 0), (4, 2), (1_000, 999)])
def test_a_slot_that_fits_no_column_takes_the_per_slot_writer(
        kind, n, slot):
    """Invalid commits still encode to what they always did (evidence
    and tests serialise commits that fail validate_basic), and the
    array path does not count them."""
    commit = random_commit(n, 38_000 + n)
    FALLS_BACK[kind](commit.signatures[slot])
    before = columnar_encodes()
    assert commit_sig_rows(commit) is None
    data = commit.to_bytes()
    digest = commit.hash()
    assert columnar_encodes() == before      # reads `columnar` 0
    assert data == per_slot_bytes(commit)
    assert digest == per_slot_hash(commit)
    assert Commit.from_bytes(data).to_bytes() == data


def test_nothing_is_kept_between_encodes():
    """CommitSig is mutable: an encode after a slot changed gives the
    changed bytes, and no object grew a field to hold bytes or
    columns."""
    commit = random_commit(1_000, 3838)
    block = _block(commit)
    first = commit.to_bytes()
    whole = block.to_bytes()
    cs = commit.signatures[500]
    cs.block_id_flag = BlockIDFlag.COMMIT
    cs.validator_address = b"\xaa" * 20
    cs.signature = b"\xbb" * 64
    cs.timestamp = 1_753_928_001 * S + 17
    second = commit.to_bytes()
    assert second != first and second == per_slot_bytes(commit)
    assert block.to_bytes() != whole
    assert Block.from_bytes(block.to_bytes()).last_commit == commit
    commit.signatures[0] = CommitSig.absent()
    assert commit.to_bytes() == per_slot_bytes(commit)
    # (Commit._hash, the memo of hash(), is older than the columns)
    assert set(vars(commit)) - {"_hash"} == {"height", "round",
                                             "block_id", "signatures"}
    assert set(vars(cs)) == {"block_id_flag", "validator_address",
                             "timestamp", "signature"}
    assert set(vars(block)) == {"header", "data", "evidence",
                                "last_commit"}


# ------------------------------------------------- pinned at the parent


def _signed_commit(n_sr: int) -> Commit:
    """A 1,000-slot commit of height 9 signed with real keys: `n_sr`
    slots drawn by a fixed seed hold sr25519 keys, the others ed25519
    (both sign deterministically here); every 50th slot is absent,
    every 17th signs nil."""
    n = 1_000
    rng = random.Random(667_333)
    sr_slots = set(rng.sample(range(n), n_sr))
    sigs = []
    for i in range(n):
        if i % 50 == 49:
            sigs.append(CommitSig.absent())
            continue
        seed = hashlib.sha256(b"commit-encode-%d" % i).digest()
        key = (sr25519.Sr25519PrivKey(seed) if i in sr_slots
               else ed25519.Ed25519PrivKey(seed))
        nil = i % 17 == 16
        ts = 1_753_928_000 * S + (i * 7_919_431) % (2 * S)
        msg = canonical.vote_sign_bytes(
            CHAIN_ID, int(VoteType.PRECOMMIT), 9, 0,
            BlockID(b"", PartSetHeader(0, b"")) if nil else BID, ts)
        sigs.append(CommitSig(
            BlockIDFlag.NIL if nil else BlockIDFlag.COMMIT,
            key.pub_key().address(), ts, key.sign(msg)))
    return Commit(9, 0, BID, sigs)


def _block(last_commit: Commit) -> Block:
    data = Data([b"tx-%d" % i for i in range(8)])
    evidence = EvidenceData([])

    def h(tag):
        return hashlib.sha256(tag).digest()

    header = Header(
        version_block=11, version_app=1, chain_id=CHAIN_ID,
        height=last_commit.height + 1,
        time=1_753_928_002 * S, last_block_id=last_commit.block_id,
        last_commit_hash=per_slot_hash(last_commit),
        data_hash=data.hash(), validators_hash=h(b"vals"),
        next_validators_hash=h(b"next-vals"), consensus_hash=h(b"params"),
        app_hash=h(b"app"), last_results_hash=h(b"results"),
        evidence_hash=evidence.hash(),
        proposer_address=h(b"proposer")[:20])
    return Block(header, data, evidence, last_commit)


PINNED = {
    # n_sr: Commit.hash(), sha256 of Commit.to_bytes(), part-set header
    # (total, hash) of the block that carries it as its LastCommit
    0: ("0eb9a30cb202b75b8f471ac66de8781141329745743136886bd80a0bd5154ced",
        "93ac593151d13da2ba9dabd96cf2e9d1eb3dd255b0947778e04a1b2e3b892485",
        2,
        "e65b48f2f339dca608e2f16eb9cc9c6e7594e4b1143af631c764bebba40b462e"),
    333: ("e55ae56006534d1e1ea1d930d7be6368c829a579feebca3bbfffe36f7281d356",
          "314bb2a2cc770e3426f8e9c3946b81790dd83cf31011b9dbb9185cdc755244b7",
          2,
          "ddc733e1adf88893ce2d829d56618774689cea7ac1d504b0d1d974db85dc08d6"),
}


@pytest.mark.parametrize("n_sr", sorted(PINNED))
def test_bytes_and_hashes_are_the_parents(n_sr):
    commit_hash, commit_sha, total, parts_hash = PINNED[n_sr]
    commit = _signed_commit(n_sr)
    before = columnar_encodes()
    data = commit.to_bytes()
    assert columnar_encodes() == before + 1
    assert hashlib.sha256(data).hexdigest() == commit_sha
    assert commit.hash().hex() == commit_hash
    assert Commit.from_bytes(data) == commit
    block = _block(commit)
    again = Block.from_bytes(block.to_bytes())
    for b in (block, again):
        header = b.make_part_set().header()
        assert (header.total, header.hash.hex()) == (total, parts_hash)
    # decoded off the wire, the commit has no memo: hash() builds its
    # leaves from the columns
    assert again.last_commit.hash().hex() == commit_hash
    assert again.header.last_commit_hash.hex() == commit_hash
    assert again.hash() == block.hash()
