"""The sr25519 challenge derived on the device (crypto/tpu/sr_verify.py
phase `sr25519.merlin`): the permutation against the host's and against
SHA3-256, the challenge against the scalar crypto/merlin.py transcript
at the rate's and the block dimension's boundaries, the launch against
sr25519_ref.verify, and what a launch records."""

import functools
import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ed
from tendermint_tpu.crypto import sr25519_ref as sr
from tendermint_tpu.crypto.merlin import Transcript
from tendermint_tpu.crypto.tpu import ledger
from tendermint_tpu.crypto.tpu import sr_verify
from tendermint_tpu.crypto.tpu import verify as tv
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER


@functools.cache
def _jitted():
    """One jit object a program, so that a shape compiles once a file."""
    import jax

    from tendermint_tpu.crypto.tpu import scalar as sc

    return (jax.jit(sr_verify.keccak_f1600),
            jax.jit(lambda b, c, s0: sc.fold_digest(
                sr_verify.challenge_digest(b, c, s0))))


def _permute(states: np.ndarray) -> np.ndarray:
    """(N, 25) uint64 through the device permutation."""
    n = len(states)
    pairs = states.view(np.uint32).reshape(n, 25, 2).transpose(1, 2, 0)
    out = np.asarray(_jitted()[0](pairs))
    return np.ascontiguousarray(out.transpose(2, 0, 1)).view(
        np.uint64).reshape(n, 25)


@pytest.mark.parametrize("lanes", [1, 7, 128])
def test_permutation_matches_the_hosts(lanes):
    from tendermint_tpu.crypto.merlin_batch import keccak_f1600_batch

    states = np.random.default_rng(lanes).integers(
        0, 2**64, size=(lanes, 25), dtype=np.uint64)
    assert (_permute(states) == keccak_f1600_batch(states)).all()


@pytest.mark.parametrize("lanes", [1, 7, 128])
def test_permutation_rebuilds_sha3_256(lanes):
    """An independent pin: SHA3-256 (rate 136, pad 0x06 .. 0x80) of b""
    and of a 200-byte message a lane, sponge by hand, against hashlib."""
    rate = 136
    rng = np.random.default_rng(1000 + lanes)
    msgs = rng.integers(0, 256, size=(lanes, 200), dtype=np.uint8)

    def absorb(state, block):
        state = state.copy()
        state[:, :block.shape[1]] ^= block
        return _permute(state.view(np.uint64)).view(np.uint8)

    empty = np.zeros((lanes, rate), np.uint8)
    empty[:, 0], empty[:, rate - 1] = 0x06, 0x80
    got = absorb(np.zeros((lanes, 200), np.uint8), empty)
    assert all(bytes(g[:32]) == hashlib.sha3_256(b"").digest() for g in got)

    last = np.zeros((lanes, rate), np.uint8)
    last[:, :200 - rate] = msgs[:, rate:]
    last[:, 200 - rate], last[:, rate - 1] = 0x06, 0x80
    got = absorb(absorb(np.zeros((lanes, 200), np.uint8), msgs[:, :rate]),
                 last)
    for g, m in zip(got, msgs):
        assert bytes(g[:32]) == hashlib.sha3_256(bytes(m)).digest()


def _scalar_challenge(ctx, msg, pk, r) -> int:
    t = Transcript(b"SigningContext")
    t.append_message(b"", ctx)
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pk)
    t.append_message(b"sign:R", r)
    return int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % ed.L


# blocks a lane at an empty context: 2 up to 128 bytes, 3 up to 294,
# 4 up to 460; 461 is the first message a launch widens for
@pytest.mark.parametrize("mlen,blocks", [
    (0, 2), (1, 2), (127, 2), (128, 2), (129, 3), (130, 3), (293, 3),
    (294, 3), (295, 4), (460, 4), (461, 5)])
def test_device_challenge_matches_scalar_transcript(mlen, blocks):
    n, bucket = 3, 8
    rng = np.random.default_rng(mlen)
    msgs = [rng.bytes(mlen) for _ in range(n)]
    pubs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    rs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    nibbles = _jitted()[1]
    for ctx in (b"", b"substrate"):
        words, counts, groups = sr_verify._challenge_blocks(
            pubs, msgs, rs, ctx, bucket)
        assert groups == 1 and not counts[n:].any()
        if not ctx:
            assert (counts[:n] == blocks).all()
        # the launch's block dimension: a power of two, 4 at the least
        assert words.shape == (bucket, 42 * (4 if counts.max() <= 4 else 8))
        got = np.asarray(nibbles(words, counts, sr_verify._prefix(ctx)[1]))
        for lane in range(n):
            k = 0
            for digit in got[:, lane]:  # MSB first
                k = 16 * k + int(digit)
            assert k < 1 << 271
            assert k % ed.L == _scalar_challenge(
                ctx, msgs[lane], bytes(pubs[lane]), bytes(rs[lane])), lane


def _signed(n, lengths, tag=b"mk"):
    minis = [hashlib.sha256(tag + b"%d" % i).digest() for i in range(n)]
    pubs = [sr.public_key_from_mini(m) for m in minis]
    msgs = [bytes([i]) * lengths[i % len(lengths)] for i in range(n)]
    sigs = [sr.sign(m, msg) for m, msg in zip(minis, msgs)]
    return pubs, msgs, sigs


def test_launch_verdicts_are_the_references_lane_for_lane():
    pubs, msgs, sigs = _signed(12, (0, 90, 128, 129, 294, 300))
    msgs[1] = b"tampered" + msgs[1][8:]                   # wrong message
    sigs[2] = b"\x01" + sigs[2][1:]                       # R odd
    sigs[3] = b"\xff" * 32 + sigs[3][32:]                 # R >= p
    s_eq_l = bytearray(ed.L.to_bytes(32, "little"))
    s_eq_l[31] |= 0x80
    sigs[4] = sigs[4][:32] + bytes(s_eq_l)                # s = L, marked
    sigs[5] = sigs[5][:63] + bytes([sigs[5][63] & 0x7F])  # marker off
    pubs[6] = pubs[6][:31]                                # short key
    sigs[7] = sigs[7][:40]                                # short signature
    got = sr_verify.verify_batch_sr(pubs, msgs, sigs)
    want = np.array([sr.verify(p, m, s)
                     for p, m, s in zip(pubs, msgs, sigs)])
    assert (got == want).all(), np.nonzero(got != want)
    assert list(np.nonzero(~got)[0]) == [1, 2, 3, 4, 5, 6, 7]


def test_launch_under_a_signing_context():
    minis = [hashlib.sha256(b"ctx%d" % i).digest() for i in range(4)]
    pubs = [sr.public_key_from_mini(m) for m in minis]
    msgs = [b"m" * (100 + 60 * i) for i in range(4)]
    sigs = [sr.sign(m, msg, b"substrate") for m, msg in zip(minis, msgs)]
    assert sr_verify.verify_batch_sr(pubs, msgs, sigs, b"substrate").all()
    assert not sr_verify.verify_batch_sr(pubs, msgs, sigs).any()


def test_one_compile_a_lane_bucket_whatever_the_lengths(monkeypatch):
    monkeypatch.setattr(tv, "_COMPILED_SHAPES", {})
    a = _signed(9, (70,))
    b = _signed(12, (0, 129, 300, 460))
    wide = _signed(5, (110, 461))
    ledger.reset()
    TRACER.clear()
    for batch in (a, b, wide):
        assert sr_verify.verify_batch_sr(*batch).all()
    recs = ledger.snapshot()
    assert [(r["kernel"], r["lanes"], r["capacity"], r["compile_cache"])
            for r in recs] == [
        ("sr25519", 9, 128, "miss"), ("sr25519", 12, 128, "hit"),
        # a message over 460 bytes is a wider launch, seen from the input
        ("sr25519", 5, 128, "miss")]
    spans = [r[6] for r in TRACER.snapshot()
             if r[0] == tracing.CRYPTO_SR_MERLIN]
    assert spans == [{"lanes": 9, "groups": 1, "blocks": 4},
                     {"lanes": 12, "groups": 4, "blocks": 4},
                     {"lanes": 5, "groups": 2, "blocks": 8}]


def test_merlin_span_is_the_layout_and_holds_no_permutation(
        monkeypatch):
    """`crypto.sr_merlin` is the layout now: once a launch, and the
    host's Keccak (the generator's signer, the tests' oracle) is not
    called under it."""
    from tendermint_tpu.crypto import merlin_batch

    def no_host_keccak(*a, **kw):
        raise AssertionError("the host permuted on the launch path")

    pubs, msgs, sigs = _signed(6, (64, 200))
    monkeypatch.setattr(merlin_batch, "keccak_f1600_batch", no_host_keccak)
    monkeypatch.setattr(merlin_batch, "sr25519_challenges", no_host_keccak)
    TRACER.clear()
    ledger.reset()
    assert sr_verify.verify_batch_sr(pubs, msgs, sigs).all()
    (span,) = [r for r in TRACER.snapshot()
               if r[0] == tracing.CRYPTO_SR_MERLIN]
    assert span[6] == {"lanes": 6, "groups": 2, "blocks": 4}
    (rec,) = ledger.snapshot()
    assert 0 < span[5] / 1e6 <= rec["stages_ms"]["pack"]


def test_one_program_builds_once_for_threads_that_ask_together():
    """What `functools.cache` alone does not promise: launch sites warmed
    side by side met the factory together and each took a jit object of
    its own (PERF.md §6, PR 36)."""
    import threading
    import time

    from tendermint_tpu.libs import jaxcache

    built = []

    def factory(k=0):
        time.sleep(0.05)
        built.append(k)
        return object()

    build = jaxcache.one_program(factory)
    gate = threading.Barrier(8)
    got = []

    def ask():
        gate.wait(timeout=10)
        got.append(build())

    threads = [threading.Thread(target=ask) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 8 and len(set(map(id, got))) == 1 and built == [0]
    assert build(1) is not got[0] and build(1) is build(1)
    assert build.__wrapped__ is factory
    build.cache_clear()
    assert build() is not got[0]


@pytest.mark.parametrize("factory", ["sr25519", "structured"])
def test_warmed_kernels_come_from_one_program(factory):
    """The two programs a mixed set's launch sites warm side by side."""
    from tendermint_tpu.crypto.tpu import expanded as ex

    build = {"sr25519": sr_verify._kernel, "structured": ex._skernel}[factory]
    assert build.__closure__ is not None and any(
        type(c.cell_contents).__name__ == "lock"
        for c in build.__closure__), "not built through one_program"
    assert build() is build()
