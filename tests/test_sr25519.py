"""sr25519 (schnorrkel/ristretto/merlin) and secp256k1 key types.

Golden anchors:
  - merlin transcript vector from the merlin crate's own test suite
  - ristretto255 small-multiple encodings from RFC 9496 §A.1
  - RIPEMD-160 standard vectors
Plus structural sign/verify/tamper coverage and the mixed-key-type
BatchVerifier path (BASELINE config #4: mixed ed25519+sr25519 set).
"""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ed25519_mod
from tendermint_tpu.crypto import ed25519_ref as ed
from tendermint_tpu.crypto import secp256k1 as secp
from tendermint_tpu.crypto import sr25519 as sr_mod
from tendermint_tpu.crypto import sr25519_ref as sr
from tendermint_tpu.crypto.batch import BatchVerifier
from tendermint_tpu.crypto.merlin import Transcript
from tendermint_tpu.crypto.secp256k1 import _ripemd160_py


def test_merlin_known_vector():
    # From merlin's tests (transcript equivalence test).
    t = Transcript(b"test protocol")
    t.append_message(b"some label", b"some data")
    c = t.challenge_bytes(b"challenge", 32)
    assert c.hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"
    )


# RFC 9496 §A.1: encodings of B, 2B, ... (first four).
_RISTRETTO_MULTIPLES = [
    "0000000000000000000000000000000000000000000000000000000000000000",
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
]


def test_ristretto_small_multiples():
    for k, want in enumerate(_RISTRETTO_MULTIPLES):
        pt = ed.scalar_mult(k, ed._B_PT) if k else ed.IDENTITY
        assert sr.ristretto_encode(pt).hex() == want, k


def test_ristretto_decode_rejects():
    assert sr.ristretto_decode(b"\x01" + bytes(31)) is None  # odd s
    assert sr.ristretto_decode((sr.P).to_bytes(32, "little")) is None
    assert sr.ristretto_decode(bytes(31)) is None  # wrong length
    # round trips
    for k in (1, 2, 3, 99, 31337):
        enc = sr.ristretto_encode(ed.scalar_mult(k, ed._B_PT))
        pt = sr.ristretto_decode(enc)
        assert pt is not None and sr.ristretto_encode(pt) == enc


def test_sr25519_sign_verify_tamper():
    mini = hashlib.sha256(b"sr-test").digest()
    pub = sr.public_key_from_mini(mini)
    msg = b"precommit h=7 r=0"
    sig = sr.sign(mini, msg)
    assert len(sig) == 64 and sig[63] & 128
    assert sr.verify(pub, msg, sig)
    assert not sr.verify(pub, msg + b"!", sig)
    bad = bytearray(sig)
    bad[5] ^= 1
    assert not sr.verify(pub, msg, bytes(bad))
    # unmarked signature rejected (schnorrkel marker bit)
    unmarked = sig[:63] + bytes([sig[63] & 0x7F])
    assert not sr.verify(pub, msg, unmarked)
    # non-canonical s rejected
    s_int = int.from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]), "little")
    s_bad = (s_int + sr.L).to_bytes(32, "little")
    if int.from_bytes(s_bad, "little") < 2**255:
        forged = bytearray(sig[:32] + s_bad)
        forged[63] |= 128
        assert not sr.verify(pub, msg, bytes(forged))


def test_sr25519_key_classes():
    pk = sr_mod.Sr25519PrivKey.from_secret(b"validator-3")
    pub = pk.pub_key()
    sig = pk.sign(b"vote")
    assert pub.verify_signature(b"vote", sig)
    assert not pub.verify_signature(b"evot", sig)
    assert len(pub.address()) == 20
    assert pub.type_name == "sr25519"
    from tendermint_tpu import crypto

    rt = crypto.pubkey_from_type_and_bytes("sr25519", pub.bytes())
    assert rt == pub


def test_secp256k1_sign_verify():
    pk = secp.Secp256k1PrivKey.from_secret(b"acct")
    pub = pk.pub_key()
    sig = pk.sign(b"tx bytes")
    assert len(sig) == 64
    assert pub.verify_signature(b"tx bytes", sig)
    assert not pub.verify_signature(b"tx bytez", sig)
    # high-S rejected even though mathematically valid
    s = int.from_bytes(sig[32:], "big")
    high = sig[:32] + (secp._N - s).to_bytes(32, "big")
    assert not pub.verify_signature(b"tx bytes", high)
    assert len(pub.address()) == 20


def test_ripemd160_vectors():
    assert _ripemd160_py(b"").hex() == (
        "9c1185a5c5e9fc54612808977ee8f548b2258d31"
    )
    assert _ripemd160_py(b"abc").hex() == (
        "8eb208f7e05d987a9b044a8e98c6b087f15a0bfc"
    )
    assert _ripemd160_py(b"a" * 1000).hex() == hashlib_ripemd(b"a" * 1000)


def hashlib_ripemd(data):
    try:
        h = hashlib.new("ripemd160")
        h.update(data)
        return h.hexdigest()
    except ValueError:
        pytest.skip("openssl lacks ripemd160; vector-only coverage")


def test_batch_verifier_mixed_key_types():
    """BASELINE config #4: one batch mixing ed25519 + sr25519 (+secp)
    lanes with per-lane verdicts in add order."""
    bv = BatchVerifier()
    expect = []
    for i in range(24):
        kind = i % 3
        msg = b"mixed %d" % i
        if kind == 0:
            k = ed25519_mod.Ed25519PrivKey.from_secret(b"e%d" % i)
        elif kind == 1:
            k = sr_mod.Sr25519PrivKey.from_secret(b"s%d" % i)
        else:
            k = secp.Secp256k1PrivKey.from_secret(b"k%d" % i)
        sig = k.sign(msg)
        if i % 5 == 0:
            msg = msg + b"~"  # tamper
        bv.add(k.pub_key(), msg, sig)
        expect.append(i % 5 != 0)
    all_ok, verdicts = bv.verify()
    assert verdicts.tolist() == expect
    assert all_ok == all(expect)
    assert not all_ok


def test_batch_verifier_all_sr25519():
    bv = BatchVerifier()
    for i in range(8):
        k = sr_mod.Sr25519PrivKey.from_secret(b"srb%d" % i)
        bv.add(k.pub_key(), b"m%d" % i, k.sign(b"m%d" % i))
    all_ok, verdicts = bv.verify()
    assert all_ok and verdicts.all() and len(verdicts) == 8


# --- schnorrkel interop anchors (offline-verifiable foreign vectors) ---

# Substrate's well-known dev accounts: secret seed -> published sr25519
# public key. Matching these 32-byte constants end-to-end pins
# ExpandEd25519 (clamp + cofactor divide), ristretto encoding, and
# scalar multiplication against the Rust `schnorrkel`/substrate
# implementations — any deviation in any layer would miss by ~2^-256.
_SUBSTRATE_DEV_KEYS = [
    ("alice",
     "e5be9a5092b81bca64be81d212e7f2f9eba183bb7a90954f7b76361f6edb5c0a",
     "d43593c715fdd31c61141abd04a99fd6822c8558854ccde39a5684e7a56da27d"),
    ("bob",
     "398f0c28f98885e046333d4a41c19cee4c37368a9832c6502f6cfd182e2aef89",
     "8eaf04151687736326c9fea17e25fc5287613693c912909cb226aa4794f26a48"),
]


def test_schnorrkel_substrate_dev_key_anchors():
    for name, seed_hex, pub_hex in _SUBSTRATE_DEV_KEYS:
        pub = sr.public_key_from_mini(bytes.fromhex(seed_hex))
        assert pub.hex() == pub_hex, name
        # and the full protocol round-trips under these keys
        msg = b"anchored message for " + name.encode()
        sig = sr.sign(bytes.fromhex(seed_hex), msg)
        assert sr.verify(pub, msg, sig)
        assert not sr.verify(pub, msg + b"!", sig)


# --- batched merlin + device group equation ---


def test_merlin_batch_matches_scalar():
    from tendermint_tpu.crypto.merlin_batch import sr25519_challenges

    n = 24
    pubs = [hashlib.sha256(b"pk%d" % i).digest() for i in range(n)]
    msgs = [b"vote " * (i % 4) + b"#%d" % i for i in range(n)]
    rs = [hashlib.sha256(b"R%d" % i).digest() for i in range(n)]
    pa = np.frombuffer(b"".join(pubs), np.uint8).reshape(n, 32)
    ra = np.frombuffer(b"".join(rs), np.uint8).reshape(n, 32)
    got = sr25519_challenges(pa, msgs, ra)
    for i in range(n):
        t = Transcript(b"SigningContext")
        t.append_message(b"", b"")
        t.append_message(b"sign-bytes", msgs[i])
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", pubs[i])
        t.append_message(b"sign:R", rs[i])
        want = int.from_bytes(t.challenge_bytes(b"sign:c", 64),
                              "little") % ed.L
        assert got[i] == want, i


@pytest.mark.slow
def test_sr25519_device_batch_parity():
    """The device group-equation kernel must agree with the host oracle
    on valid lanes and every corruption mode."""
    from tendermint_tpu.crypto.tpu.sr_verify import verify_batch_sr

    n = 16
    minis = [hashlib.sha256(b"bk%d" % i).digest() for i in range(n)]
    pubs = [sr.public_key_from_mini(m) for m in minis]
    msgs = [b"precommit h=%d" % i for i in range(n)]
    sigs = [sr.sign(m, msg) for m, msg in zip(minis, msgs)]

    sigs[1] = sigs[1][:32] + bytes(31) + b"\x80"  # s = 0
    msgs[2] = b"tampered"
    sigs[3] = bytes(32) + sigs[3][32:]  # R = identity encoding
    sigs[4] = sigs[4][:63] + bytes([sigs[4][63] & 0x7F])  # marker off
    pubs[5] = b"\xff" * 32  # non-canonical pk encoding
    sigs[6] = b"\x01" + sigs[6][1:]  # R odd (non-canonical ristretto)
    s_eq_l = bytearray((ed.L).to_bytes(32, "little"))
    s_eq_l[31] |= 0x80  # marker bit on top of a non-canonical s = L
    sigs[7] = sigs[7][:32] + bytes(s_eq_l)

    got = verify_batch_sr(pubs, msgs, sigs)
    want = np.array(
        [sr.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)])
    assert (got == want).all(), np.nonzero(got != want)
    assert got[0] and not got[1:8].any()


def test_batch_verifier_routes_sr25519_to_device():
    """>= _DEVICE_THRESHOLD_SR sr25519 lanes take the device path
    inside the product BatchVerifier (BASELINE config #4 mixed
    batches) — asserted via the backend lane counter, so a silent
    host fallback cannot fake a pass."""
    from tendermint_tpu.crypto import batch as batch_mod
    from tendermint_tpu.libs.metrics import crypto_metrics

    batch_mod.reset_breakers()  # clear any breaker state from
    # earlier tests — this test is about routing, not degradation
    n = batch_mod._DEVICE_THRESHOLD_SR + 16
    # lanes are labelled with the platform that ran them
    from tendermint_tpu.crypto.tpu import backend as tpu_backend

    label = f"{tpu_backend.platform()}-sr25519"
    assert label == "cpu-sr25519"
    lanes_before = crypto_metrics().batch_lanes.value(backend=label)
    minis = [hashlib.sha256(b"rt%d" % i).digest() for i in range(n)]
    bv = BatchVerifier()
    for i, mini in enumerate(minis):
        pk = sr_mod.Sr25519PubKey(sr.public_key_from_mini(mini))
        msg = b"mixed batch %d" % i
        sig = sr.sign(mini, msg)
        if i == 9:
            sig = sig[:32] + bytes(31) + b"\x80"
        bv.add(pk, msg, sig)
    ok, verdicts = bv.verify()
    assert not ok
    want = np.ones(n, bool)
    want[9] = False
    assert (verdicts == want).all()
    assert (crypto_metrics().batch_lanes.value(backend=label)
            == lanes_before + n), "sr25519 lanes did not take the device path"
