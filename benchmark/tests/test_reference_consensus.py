"""The copied round-0 follower (`reference/consensus_model.py`) against
hand-made heights: its sign bytes against the program's for both vote
types, the polka and the commit at MORE than 2/3 and not at 2/3, a
spoiled signature and a second vote of a validator, and the app hash."""

import hashlib

from benchmark.reference import consensus_model as model
from benchmark.reference import ed25519_zip215 as ref

CHAIN = "model-test"
BLOCK, PARTS = hashlib.sha256(b"block").digest(), hashlib.sha256(b"p").digest()


def _set(n):
    seeds = [hashlib.sha256(b"v%d" % i).digest() for i in range(n)]
    return seeds, [(ref.public_key_from_seed(s), 1) for s in seeds]


def _vote(seeds, vtype, i, height=3, when=1_700_000_000_123_456_789):
    msg = model.vote_sign_bytes(CHAIN, vtype, height, BLOCK, 2, PARTS, when)
    return (vtype, i, when, ref.sign(seeds[i], msg))


def test_sign_bytes_equal_the_programs_for_both_types():
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    bid = BlockID(BLOCK, PartSetHeader(2, PARTS))
    for vtype in (model.PREVOTE, model.PRECOMMIT):
        for when in (1, 1_700_000_000_000_000_000, 1_700_000_000_999_999_999):
            assert model.vote_sign_bytes(
                CHAIN, vtype, 7, BLOCK, 2, PARTS, when) == \
                canonical.vote_sign_bytes(CHAIN, vtype, 7, 0, bid, when)


def test_commit_needs_more_than_two_thirds():
    seeds, vals = _set(6)
    f = model.Follower(CHAIN, vals)
    four = [_vote(seeds, t, i) for t in (1, 2) for i in range(4)]
    out = f.follow(3, BLOCK, 2, PARTS, [b"a=1"], four)
    assert not out.polka and out.block_hash is None      # 4 of 6 = 2/3
    assert out.app_hash == bytes(8)
    five = four + [_vote(seeds, t, 4) for t in (1, 2)]
    out = f.follow(3, BLOCK, 2, PARTS, [b"a=1", b"b"], five)
    assert out.polka and out.block_hash == BLOCK
    assert out.app_hash == (2).to_bytes(8, "big")
    assert out.members == {1: set(range(5)), 2: set(range(5))}
    assert f.holds_two_thirds(range(5)) and not f.holds_two_thirds(range(4))


def test_a_spoiled_copy_is_refused_and_the_good_one_still_counts():
    seeds, vals = _set(4)
    good = _vote(seeds, model.PRECOMMIT, 2)
    bad = bytearray(good[3])
    bad[40] ^= 1
    spoiled = (good[0], good[1], good[2], bytes(bad))
    votes = [spoiled] + [_vote(seeds, model.PRECOMMIT, i) for i in range(4)]
    votes.append(spoiled)          # after the good copy: not even looked at
    out = model.Follower(CHAIN, vals).follow(3, BLOCK, 2, PARTS, [], votes)
    assert out.refused == [(2, 2, bytes(bad))]
    assert out.members[2] == {0, 1, 2, 3} and out.block_hash == BLOCK
    assert not out.polka


def test_a_vote_for_another_height_does_not_verify():
    seeds, vals = _set(4)
    votes = [_vote(seeds, model.PREVOTE, i, height=4) for i in range(4)]
    out = model.Follower(CHAIN, vals).follow(3, BLOCK, 2, PARTS, [], votes)
    assert len(out.refused) == 4 and not out.polka
