"""The mixed cell's plain references against their published vectors
and against the program's own host oracles on seeded cases; the
generator's bulk sr25519 signer against the program's verifier; and
`ops_sr.py`'s count against a hand count."""

import hashlib

import pytest

from benchmark.reference import ed25519_zip215 as ed
from benchmark.reference import evidence_model as em
from benchmark.reference import sr25519_schnorrkel as ref

# RFC 9496 A.1: encodings of 0, B, 2B, 3B
RISTRETTO_MULTIPLES = [
    "0000000000000000000000000000000000000000000000000000000000000000",
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
]
# Substrate's well-known dev account "alice": mini secret -> sr25519
# public key as Rust `schnorrkel` derives it
ALICE = ("e5be9a5092b81bca64be81d212e7f2f9eba183bb7a90954f7b76361f6edb5c0a",
         "d43593c715fdd31c61141abd04a99fd6822c8558854ccde39a5684e7a56da27d")


def test_merlin_published_vector():
    # merlin's own transcript equivalence test
    t = ref.Transcript(b"test protocol")
    t.append(b"some label", b"some data")
    assert t.challenge(b"challenge", 32).hex() == \
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"


def test_ristretto_multiples_and_round_trips():
    for k, want in enumerate(RISTRETTO_MULTIPLES):
        pt = ed.scalar_mult(k, ed._B_PT) if k else ed.IDENTITY
        assert ref.encode(pt).hex() == want
        assert ref.encode(ref.decode(bytes.fromhex(want))).hex() == want
    assert ref.decode(b"\x01" + bytes(31)) is None       # negative s
    assert ref.decode(ref.P.to_bytes(32, "little")) is None
    assert ref.decode(b"\xff" * 32) is None


def test_schnorrkel_anchored_key():
    assert ref.public_key(bytes.fromhex(ALICE[0])).hex() == ALICE[1]


def _cases(n=10):
    for i in range(n):
        mini = hashlib.sha256(b"refmix%d" % i).digest()
        yield mini, b"vote %d " % i * (1 + i % 4)


def test_verify_agrees_with_the_programs_oracle_both_ways():
    from tendermint_tpu.crypto import sr25519_ref as prog

    for mini, msg in _cases():
        pub = ref.public_key(mini)
        assert pub == prog.public_key_from_mini(mini)
        theirs, ours = prog.sign(mini, msg), ref.sign(mini, msg)
        spoiled = [
            theirs[:40] + bytes([theirs[40] ^ 1]) + theirs[41:],   # s
            bytes([theirs[0] ^ 2]) + theirs[1:],                   # R
            theirs[:63] + bytes([theirs[63] & 0x7F]),              # marker
            theirs[:32] + (ref.L).to_bytes(32, "little")[:31]
            + bytes([0x90]),                                       # s >= L
        ]
        for sig in (theirs, ours, *spoiled):
            for m in (msg, msg + b"!"):
                assert ref.verify(pub, m, sig) == prog.verify(pub, m, sig)
        assert ref.verify(pub, msg, theirs) and ref.verify(pub, msg, ours)
        assert not any(ref.verify(pub, msg, s) for s in spoiled)


def test_bulk_signer_signs_what_the_programs_verifier_takes():
    from benchmark.traffic.mixed_evidence_replay import SrSigner
    from tendermint_tpu.crypto import sr25519_ref as prog

    minis = {i: hashlib.sha256(b"bulk%d" % i).digest() for i in (3, 5, 8)}
    signer = SrSigner(77, minis)
    numbers = [3, 5, 8, 5, 3, 8, 8]
    msgs = [b"m" * (10 + j % 3) + b"%d" % j for j in range(len(numbers))]
    sigs = signer.sign(numbers, msgs, "test")
    assert len(set(s[:32] for s in sigs)) == len(sigs)   # distinct nonces
    for number, msg, sig in zip(numbers, msgs, sigs):
        pub = signer.pubs[number]
        assert pub == prog.public_key_from_mini(minis[number])
        assert prog.verify(pub, msg, sig) and ref.verify(pub, msg, sig)
        assert not prog.verify(pub, msg + b"x", sig)
    assert signer.sign(numbers, msgs, "other") != sigs
    assert signer.sign([], [], "none") == []


def test_evidence_model_sign_bytes_are_the_programs():
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType

    for vtype, when in ((VoteType.PREVOTE, 1_753_928_000_000_000_007),
                        (VoteType.PRECOMMIT, 5_000_000_000), (1, 0)):
        bid = em.BlockId(b"\x11" * 32, 7, b"\x22" * 32)
        vote = Vote(type=VoteType(vtype), height=41, round=0,
                    block_id=BlockID(bid.hash, PartSetHeader(7, bid.parts_hash)),
                    timestamp=when, validator_address=b"a" * 20,
                    validator_index=0)
        assert em.vote_sign_bytes("chain-x", int(vtype), 41, 0, bid, when) \
            == vote.sign_bytes("chain-x")


def _model_case():
    """Two validators (one of each key type), heights 1-3, one sound
    evidence each."""
    minis = [hashlib.sha256(b"em%d" % i).digest() for i in range(2)]
    keys = [("ed25519", ed.public_key_from_seed(minis[0])),
            ("sr25519", ref.public_key(minis[1]))]
    sign = [lambda m: ed.sign(minis[0], m), lambda m: ref.sign(minis[1], m)]
    vals = {bytes([i]) * 20: (k, pk, 10 + i)
            for i, (k, pk) in enumerate(keys)}
    model = em.EvidenceModel("c", {h: vals for h in (1, 2, 3)},
                             {1: 100, 2: 200, 3: 300}, (100000, 10**18))
    evs = []
    for i in range(2):
        votes = []
        for tag in (1, 2):
            bid = em.BlockId(bytes([tag]) * 32, 1, bytes([tag]) * 32)
            msg = em.vote_sign_bytes("c", em.PREVOTE, 2, 0, bid, 150 + i)
            votes.append(em.Vote(em.PREVOTE, 2, 0, bid, 150 + i,
                                 bytes([i]) * 20, sign[i](msg)))
        evs.append(em.DuplicateVote(votes[0], votes[1], 21, 10 + i, 200))
    return model, evs


def test_evidence_model_follows_the_reference_loop():
    model, evs = _model_case()
    assert model.check_block(evs, 3, 300) is None
    assert model.check_block(evs + evs[:1], 3, 300) == \
        (2, "duplicate evidence in block")

    def spoiled(ev, **kw):
        return ev._replace(**kw)

    def bad_sig(vote):
        s = vote.signature
        return vote._replace(signature=s[:40] + bytes([s[40] ^ 1]) + s[41:])

    for i in (0, 1):     # each key type
        for which, field in (("A", "vote_a"), ("B", "vote_b")):
            bad = spoiled(evs[i], **{field: bad_sig(getattr(evs[i], field))})
            got = model.check_block([evs[1 - i], bad], 3, 300)
            assert got == (1, f"invalid signature on vote {which}")
            # a lane nobody verifies is a lane that passes
            assert model.check_block([bad], 3, 300, lanes=set()) is None
            assert model.check_block(
                [bad], 3, 300, lanes={(0, which)}) == (0, got[1])
        bad_b = spoiled(evs[i], vote_b=bad_sig(evs[i].vote_b))
        assert model.check_block([bad_b], 3, 300,
                                 weak="evidence_first_vote_only") is None
        assert model.check_block([bad_b], 3, 300,
                                 weak="skips_evidence_signatures") is None
    assert model.check_block(
        [spoiled(evs[0], validator_power=99)], 3, 300)[1] == \
        "validator power mismatch"
    assert model.check_block(
        [spoiled(evs[0], total_voting_power=1)], 3, 300)[1] == \
        "total voting power mismatch"
    assert model.check_block([spoiled(evs[0], timestamp=1)], 3, 300)[1] == \
        "evidence time != block time"
    assert model.check_block(
        [spoiled(evs[0], vote_a=evs[0].vote_b, vote_b=evs[0].vote_a)],
        3, 300)[1] == "duplicate votes in wrong order or identical"
    old = em.EvidenceModel("c", model.validators, model.block_times, (0, 1))
    assert "too old" in old.check_block(evs, 3, 300)[1]
    model.commit_block(evs[:1])
    assert model.check_block(evs, 3, 300) == \
        (0, "evidence was already committed")
    assert model.check_block(evs[1:], 3, 300) is None


def test_evidence_model_agrees_with_the_programs_verify():
    """The same sound and spoiled evidence through the program's
    one-evidence path."""
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PubKey
    from tendermint_tpu.evidence.verify import (
        EvidenceError, verify_duplicate_vote)
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.evidence import DuplicateVoteEvidence
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    model, evs = _model_case()
    by_kind = {"ed25519": Ed25519PubKey, "sr25519": Sr25519PubKey}
    vals = ValidatorSet([])
    vals.validators = [
        Validator(address=a, pub_key=by_kind[k](pk), voting_power=pw)
        for a, (k, pk, pw) in model.validators[2].items()]

    def theirs(ev):
        def vote(v):
            return Vote(type=VoteType(v.type), height=v.height,
                        round=v.round, block_id=BlockID(
                            v.block_id.hash, PartSetHeader(
                                v.block_id.parts_total,
                                v.block_id.parts_hash)),
                        timestamp=v.timestamp,
                        validator_address=v.validator, validator_index=0,
                        signature=v.signature)
        return DuplicateVoteEvidence(vote(ev.vote_a), vote(ev.vote_b),
                                     ev.total_voting_power,
                                     ev.validator_power, ev.timestamp)

    for ev in evs:
        s = ev.vote_b.signature
        bad = ev._replace(vote_b=ev.vote_b._replace(
            signature=s[:40] + bytes([s[40] ^ 1]) + s[41:]))
        verify_duplicate_vote(theirs(ev), "c", vals, 200)
        assert model.check_block([bad], 3, 300)[1] == \
            "invalid signature on vote B"
        with pytest.raises(EvidenceError, match="signature on vote B"):
            verify_duplicate_vote(theirs(bad), "c", vals, 200)


def test_ops_sr_count_is_the_hand_count():
    from benchmark import ops_sr

    # two decodes: 29 multiplies and 257 squarings each (the power's
    # 11 + 251, 8 + 3 more in sqrt_ratio_m1, 10 + 3 more in decode)
    assert ops_sr.DECODE == (29, 257)
    # 14 adds for the table; 64 x (4 doublings + add + add_z1); one
    # last add; the equality
    muls = 2 * 29 + 14 * 9 + 64 * (4 * 4 + 9 + 8) + 9 + 4
    sqrs = 2 * 257 + 64 * 4 * 4
    assert (muls, sqrs) == (2309, 1538) == ops_sr.lane_field_ops()
    assert ops_sr.lane_ops() == 2 * (2309 * 484 + 1538 * 253) == 3_013_340
    assert ops_sr.lane_bytes() == 580
    assert ops_sr.launch_bytes() == 64 * 16 * 3 * 22 * 4
    roof = ops_sr.roofline("TPU v5 lite", 4096)
    assert roof["bound"] == "int8_peak"
    assert roof["least_s"] == pytest.approx(4096 * 3_013_340 / 393e12)
    with pytest.raises(KeyError):
        ops_sr.roofline("cpu", 1)


def test_ops_sr_counts_the_kernels_multiplies():
    """The hand count against the kernel itself: field multiplies and
    squarings of the traced program (a fori_loop's body counted once a
    window)."""
    import jax
    import numpy as np

    from benchmark import ops_sr
    from tendermint_tpu.crypto.tpu import sr_verify
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.crypto.tpu.fieldsel import F as fe

    calls = {"mul": 0, "sqr": 0}
    real = {"mul": fe.mul, "sqr": fe.sqr}
    depth = []   # inside a mul or sqr: its own helpers do not count

    def counted(name):
        def f(*a, **kw):
            if not depth:
                calls[name] += 1
            depth.append(name)
            try:
                return real[name](*a, **kw)
            finally:
                depth.pop()
        return f

    for name in real:
        setattr(fe, name, counted(name))
    try:
        sr_verify._kernel.cache_clear()
        n = 8
        jax.make_jaxpr(sr_verify._kernel())(
            np.zeros((n, 32), np.uint8), np.zeros((n, 32), np.uint8),
            np.zeros((64, n), np.int32), np.zeros((64, n), np.int32),
            np.ones(n, bool), np.ones(n, bool), np.ones(n, bool),
            tv.b_comb_tables()[:64])
    finally:
        for name, f in real.items():
            setattr(fe, name, f)
        sr_verify._kernel.cache_clear()
    # traced once: the loop's body stands for its 64 windows, the two
    # decodes of a lane are ONE pass over 2N lanes, and the power's
    # eight runs of squarings (5 + 10 + 20 + 10 + 50 + 100 + 50 + 2)
    # are loops whose body is traced once each
    body = (4 * 4 + 9 + 8, 4 * 4)
    muls, sqrs = ops_sr.lane_field_ops()
    assert calls["mul"] == muls - 63 * body[0] - ops_sr.DECODE[0]
    assert calls["sqr"] == sqrs - 63 * body[1] - ops_sr.DECODE[1] \
        - (247 - 8)


def test_roofline_reader_takes_lanes_and_time_from_the_same_launches():
    """The slice's executions are LastCommit launches (333 lanes) of a
    window whose other launches are larger: the lanes are those of the
    ledger's records from where the slice begins, as many as the slice
    has executions; without the driver's counter, the window's mean."""
    from benchmark import ops_sr
    from benchmark.layer_metrics import trace_roofline_sr

    class Readings:
        device_kind = "TPU v5 lite"
        trace = {"modules": {"jit_sr25519_kernel": {"count": 2,
                                                    "total_s": 0.0084}}}
        counters = {"trace_slice_from_mono": 100.0}
        ledger = [
            {"kernel": "sr25519", "workload": "fastsync", "lanes": 3584,
             "mono": 98.0},
            {"kernel": "structured", "workload": "consensus", "lanes": 667,
             "mono": 100.1},
            {"kernel": "sr25519", "workload": "consensus", "lanes": 333,
             "mono": 100.2},
            {"kernel": "sr25519", "workload": "consensus", "lanes": 333,
             "mono": 100.4},
            {"kernel": "sr25519", "workload": "evidence", "lanes": 660,
             "mono": 100.6}]

        def ledger_for(self, workload=None, kernel=None):
            return [r for r in self.ledger if kernel in (None, r["kernel"])]

    params = {"pattern": "sr25519_kernel", "kernel": "sr25519"}
    share, note = trace_roofline_sr.read(Readings(), params)
    assert (note["lanes_per_launch"], note["lanes_from"],
            note["launches_counted"]) == (333, "slice", 2)
    least = ops_sr.roofline("TPU v5 lite", 333)["least_s"]
    assert share == pytest.approx(100 * least / 0.0042)
    r = Readings()
    r.counters = {}
    _, note = trace_roofline_sr.read(r, params)
    assert (note["lanes_from"], note["launches_counted"]) == ("window", 4)
