"""The planted-fault generators against the copied reference, the
controls against the same cases, and the copies against the program's
originals (a copy that drifted would compare the program with nothing)."""

import hashlib

from benchmark import gen, ops
from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref
from benchmark.reference.kvstore_model import KVStoreModel

SEED = hashlib.sha256(b"benchmark tests").digest()
PUB = ref.public_key_from_seed(SEED)
MSG = b"a precommit's sign bytes"
SIG = ref.sign(SEED, MSG)


def test_sound_signature_and_each_corruption():
    assert ref.verify(PUB, MSG, SIG)
    assert not ref.verify(PUB, MSG + b"!", SIG)          # the message
    for kind in ("r_bit", "s_bit", "s_plus_l"):
        assert not ref.verify(PUB, MSG, gen.corrupt(SIG, kind)), kind


def test_each_control_is_caught_by_its_planted_case():
    # no S < L check: S + L is the same point, so it slips through
    assert ref.verify(PUB, MSG, gen.corrupt(SIG, "s_plus_l"), check_s=False)
    # strict RFC 8032 refuses what consensus must accept
    z = gen.zip215_only(SEED, PUB, MSG)
    assert ref.verify(PUB, MSG, z)
    assert not ref.verify(PUB, MSG, z, strict=True)
    # and agrees on everything canonical
    assert ref.verify(PUB, MSG, SIG, strict=True)


def test_reference_equals_the_programs_oracle():
    from tendermint_tpu.crypto import ed25519_ref as theirs

    z = gen.zip215_only(SEED, PUB, MSG)
    for sig in (SIG, z, gen.corrupt(SIG, "r_bit"),
                gen.corrupt(SIG, "s_bit"), gen.corrupt(SIG, "s_plus_l")):
        assert ref.verify(PUB, MSG, sig) == theirs.verify(PUB, MSG, sig)


def test_sign_bytes_equal_the_programs():
    from tendermint_tpu.types import canonical as theirs
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    bid = BlockID(hash=b"\xab" * 32,
                  part_set_header=PartSetHeader(4, b"\xcd" * 32))
    for height, rnd, ts in ((5, 0, 1_753_928_000_123_456_789),
                            (123456, 2, 1_753_928_000_000_000_000),
                            (7, 0, 0)):
        pre, suf = canonical.vote_sign_parts(
            "bench-chain", height, rnd, b"\xab" * 32, 4, b"\xcd" * 32)
        assert canonical.with_timestamp(pre, suf, ts) == \
            theirs.vote_sign_bytes("bench-chain", 2, height, rnd, bid, ts)


def test_envelope_and_kvstore_model_equal_the_programs():
    from benchmark.traffic import kvstore_loadgen as lg
    from tendermint_tpu.abci import types as t
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.types import tx_envelope

    assert lg.envelope(PUB, SIG, b"p" * 100) == \
        tx_envelope.encode(PUB, SIG, b"p" * 100)
    assert lg.SIGN_DOMAIN + b"x" == tx_envelope.sign_bytes(b"x")
    app, model = KVStoreApp(), KVStoreModel()
    for tx in (b"a=1", b"a=2", b"plain", b"k=v=w"):
        app.deliver_tx(t.RequestDeliverTx(tx))
        model.deliver(tx)
    assert app.commit(t.RequestCommit()).data == model.app_hash()
    for key, value in model.values.items():
        assert app.query(t.RequestQuery(data=key)).value == value


def test_ops_against_a_hand_count():
    # one lane by hand: 69 windows x (9 + 8) multiplies, two adds, three
    # doublings (4 M + 4 S each), one decompression (20 M + 255 S)
    muls = 69 * 17 + 18 + 12 + 20
    sqrs = 12 + 255
    assert ops.expanded_lane_field_ops() == (muls, sqrs) == (1223, 267)
    assert ops.expanded_lane_ops() == 2 * (1223 * 484 + 267 * 253)
    assert ops.expanded_lane_bytes(112) == 69 * 512 + 64 + 4 + 112 + 1
    roof = ops.roofline("TPU v5 lite", 128, 112)
    assert roof["bound"] == "hbm_bandwidth"
    assert abs(roof["bytes_s"] - 128 * 35509 / 819e9) < 1e-12
    try:
        ops.peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
