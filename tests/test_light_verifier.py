"""Verifier boundary cases (light/verifier.py; reference:
light/verifier_test.go table rows this suite pins exactly at the
edge): trusting-period expiry AT the boundary instant, max-clock-drift
AT the boundary instant, non-monotonic header time rejection, and
`NewValSetCantBeTrustedError` driving the client's bisection (the
serving plane routes the same error classes — test_light_serving.py holds
the plane-side parity test).

Everything here runs on MockPV/ref-ed25519 fixtures; the one test
that exercises the OpenSSL signing path importorskips `cryptography`
(absent in the growth container) so it skips cleanly, not errors."""

import pytest

from tendermint_tpu.light import (
    LightBlock, SignedHeader, verify_adjacent, verify_non_adjacent,
)
from tendermint_tpu.light.errors import (
    NewValSetCantBeTrustedError,
    OutsideTrustingPeriodError,
    VerificationFailedError,
)
from tendermint_tpu.light.verifier import MAX_CLOCK_DRIFT_NS
from tendermint_tpu.types.block import BlockID, Header, PartSetHeader

from helpers import CHAIN_ID, sign_commit
from test_light import HOUR, NOW, T0, LightChain, _client, _valset, run

DRIFT = MAX_CLOCK_DRIFT_NS


def _mini_chain(times):
    """LightChain with EXPLICIT per-height header times (the stock
    fixture is strictly monotonic, so non-monotonic rejections need
    their own, properly signed, headers)."""
    n = len(times)
    sets = {h: _valset(tuple(range(4))) for h in range(1, n + 2)}
    blocks = {}
    prev_bid = None
    for h in range(1, n + 1):
        vals, pvs = sets[h]
        nvals, _ = sets[h + 1]
        header = Header(
            version_block=11, version_app=0, chain_id=CHAIN_ID,
            height=h, time=times[h - 1], last_block_id=prev_bid,
            last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
            validators_hash=vals.hash(),
            next_validators_hash=nvals.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"\x04" * 32,
            last_results_hash=b"\x05" * 32,
            evidence_hash=b"\x06" * 32,
            proposer_address=vals.get_proposer().address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
        commit = sign_commit(vals, pvs, CHAIN_ID, h, 0, bid,
                             header.time + 1)
        blocks[h] = LightBlock(SignedHeader(header, commit), vals)
        prev_bid = bid
    return blocks


def test_trusting_period_expiry_boundary():
    """HeaderExpired is `trusted.time + period <= now`: the EXACT
    boundary instant already rejects (the valset may unbond the
    nanosecond the period ends), one ns inside still verifies."""
    c = LightChain(8)
    t1 = c.blocks[1].time()
    verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[8], HOUR,
                        t1 + HOUR - 1)
    with pytest.raises(OutsideTrustingPeriodError):
        verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[8], HOUR,
                            t1 + HOUR)
    # the adjacent path applies the same expiry rule
    with pytest.raises(OutsideTrustingPeriodError):
        verify_adjacent(CHAIN_ID, c.blocks[1], c.blocks[2], HOUR,
                        t1 + HOUR)


def test_max_clock_drift_boundary():
    """From-the-future is `untrusted.time >= now + drift`: a header
    timestamped exactly `now + drift` rejects, one ns under the drift
    allowance verifies."""
    c = LightChain(8)
    t8 = c.blocks[8].time()
    with pytest.raises(VerificationFailedError, match="future"):
        verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[8], HOUR,
                            t8 - DRIFT)
    verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[8], HOUR,
                        t8 - DRIFT + 1)
    with pytest.raises(VerificationFailedError, match="future"):
        verify_adjacent(CHAIN_ID, c.blocks[1], c.blocks[2], HOUR,
                        c.blocks[2].time() - DRIFT)


def test_non_monotonic_header_time_rejected():
    """A properly SIGNED header whose time is not strictly after the
    trusted header's is refused before any signature work — equal
    times reject too (the chain clock must advance)."""
    # 4 goes back behind 2: the 2 -> 4 skip must reject on time
    blocks = _mini_chain([T0, T0 + 10, T0 + 5, T0 + 7])
    now = T0 + HOUR // 2
    with pytest.raises(VerificationFailedError, match="time"):
        verify_non_adjacent(CHAIN_ID, blocks[2], blocks[4], HOUR, now)
    # the adjacent path rejects a stalled clock (equal times) too
    equal = _mini_chain([T0, T0 + 10, T0 + 10])
    with pytest.raises(VerificationFailedError, match="time"):
        verify_adjacent(CHAIN_ID, equal[2], equal[3], HOUR, now)
    # and height must advance as well: same-height / older targets
    # are structural failures, not crypto ones
    with pytest.raises(VerificationFailedError, match="height"):
        verify_non_adjacent(CHAIN_ID, blocks[2], blocks[2], HOUR, now)


def test_cant_trust_drives_bisection():
    """A valset rotation leaving < trust-level overlap across the gap:
    the direct skipping verify raises NewValSetCantBeTrustedError, and
    the client turns exactly that error into bisection — landing on
    the adjacent transition where next_validators_hash takes over —
    and verifies the same target the one-shot verify refused."""
    rotate = lambda h: tuple(range(4)) if h <= 8 else (3, 4, 5, 6)
    c = LightChain(16, valset_for=rotate)
    # 1 of 4 equal-power validators overlap: 25% < 1/3
    with pytest.raises(NewValSetCantBeTrustedError):
        verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[16], HOUR,
                            NOW)
    fetched = []
    base = c.provider()

    class Logging(type(base)):
        async def light_block(self, height):
            fetched.append(height)
            return await base.light_block(height)

    cl = _client(c, primary=Logging())
    lb = run(cl.verify_light_block_at_height(16))
    assert lb.hash() == c.blocks[16].hash()
    # bisection actually happened: pivot heights strictly between the
    # trust root and the target were fetched, and the store holds the
    # verified pivots it walked through
    assert any(1 < h < 16 for h in fetched)
    assert cl.store.get(16) is not None


def test_verifier_with_openssl_signing_path():
    """The same boundary semantics hold for commits signed through the
    OpenSSL (`cryptography`) ed25519 path — skipped cleanly where the
    package is absent (this container's seed state)."""
    pytest.importorskip("cryptography")
    from tendermint_tpu.crypto import ed25519 as ed

    if not ed._HAVE_OPENSSL:
        pytest.skip("cryptography present but OpenSSL path disabled")
    c = LightChain(4)
    verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[4], HOUR, NOW)
    with pytest.raises(OutsideTrustingPeriodError):
        verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[4], HOUR,
                            c.blocks[1].time() + HOUR)
