"""Light client: verifier rules, bisection, witness divergence, and
verification against a live node (reference: light/verifier_test.go,
client_test.go, detector_test.go)."""

import asyncio

import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.light import (
    BlockStoreProvider, Client, DivergenceError, LightBlock, LightStore,
    SignedHeader, TrustOptions, verify_adjacent, verify_non_adjacent,
)
from tendermint_tpu.light.errors import (
    LightClientError, NewValSetCantBeTrustedError,
    OutsideTrustingPeriodError, VerificationFailedError,
)
from tendermint_tpu.light.provider import BlockNotFoundError, Provider
from tendermint_tpu.types.block import BlockID, Header, PartSetHeader
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.validator import Validator

from helpers import CHAIN_ID, deterministic_pv, sign_commit

HOUR = 3600 * 1_000_000_000
T0 = 1_700_000_000 * 1_000_000_000


def _valset(indices):
    vals = [Validator.new(deterministic_pv(i).get_pub_key(), 10)
            for i in indices]
    return ValidatorSet(vals), [deterministic_pv(i) for i in indices]


class LightChain:
    """Deterministic header chain with per-height validator sets."""

    def __init__(self, n_heights, valset_for=lambda h: tuple(range(4))):
        self.blocks: dict[int, LightBlock] = {}
        sets = {h: _valset(valset_for(h))
                for h in range(1, n_heights + 2)}
        prev_bid = None
        for h in range(1, n_heights + 1):
            vals, pvs = sets[h]
            nvals, _ = sets[h + 1]
            header = Header(
                version_block=11, version_app=0, chain_id=CHAIN_ID,
                height=h, time=T0 + h * 1_000_000_000,
                last_block_id=prev_bid,
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
            self.blocks[h] = LightBlock(SignedHeader(header, commit), vals)
            prev_bid = bid

    def provider(self, tamper_height=None):
        chain = self

        class P(Provider):
            async def light_block(self, height):
                if height == 0:
                    height = max(chain.blocks)
                lb = chain.blocks.get(height)
                if lb is None:
                    raise BlockNotFoundError(str(height))
                if height == tamper_height:
                    h2 = lb.signed_header.header
                    import dataclasses
                    forged = dataclasses.replace(h2, app_hash=b"\xee" * 32)
                    return LightBlock(
                        SignedHeader(forged, lb.signed_header.commit),
                        lb.validator_set)
                return lb

        return P()


NOW = T0 + 100 * 1_000_000_000


def test_verify_adjacent_ok_and_failures():
    c = LightChain(3)
    b1, b2 = c.blocks[1], c.blocks[2]
    verify_adjacent(CHAIN_ID, b1, b2, HOUR, NOW)
    # expired trusting period
    with pytest.raises(OutsideTrustingPeriodError):
        verify_adjacent(CHAIN_ID, b1, b2, 1, NOW)
    # non-adjacent heights refused by the adjacent path
    with pytest.raises(VerificationFailedError, match="adjacent"):
        verify_adjacent(CHAIN_ID, b1, c.blocks[3], HOUR, NOW)
    # tampered header: commit no longer matches
    import dataclasses
    forged_header = dataclasses.replace(b2.signed_header.header,
                                        app_hash=b"\xee" * 32)
    forged = LightBlock(SignedHeader(forged_header,
                                     b2.signed_header.commit),
                        b2.validator_set)
    with pytest.raises(Exception):
        verify_adjacent(CHAIN_ID, b1, forged, HOUR, NOW)


def test_verify_non_adjacent_trust_overlap():
    # constant valset: full overlap, skipping succeeds across the gap
    c = LightChain(10)
    verify_non_adjacent(CHAIN_ID, c.blocks[1], c.blocks[10], HOUR, NOW)
    # complete valset replacement mid-chain: no overlap → can't trust
    c2 = LightChain(10, valset_for=lambda h: tuple(range(4)) if h <= 5
                    else tuple(range(10, 14)))
    with pytest.raises(NewValSetCantBeTrustedError):
        verify_non_adjacent(CHAIN_ID, c2.blocks[1], c2.blocks[10],
                            HOUR, NOW)


def run(coro):
    return asyncio.run(coro)


def _client(chain, trust_height=1, witnesses=(), primary=None):
    return Client(
        CHAIN_ID,
        TrustOptions(period_ns=HOUR, height=trust_height,
                     hash=chain.blocks[trust_height].hash()),
        primary or chain.provider(),
        list(witnesses),
        LightStore(MemDB()),
        now_fn=lambda: NOW,
    )


def test_client_sequential_and_skipping():
    chain = LightChain(20)
    cl = _client(chain)
    lb = run(cl.verify_light_block_at_height(20))
    assert lb.height() == 20
    # everything verified landed in the trusted store
    assert cl.store.latest_height() == 20


def test_client_bisection_through_valset_rotation():
    # valset rotates one member every height: adjacent fully verifiable,
    # distant jumps lose 1/3 overlap and force bisection
    chain = LightChain(
        16, valset_for=lambda h: tuple(range(h, h + 4)))
    cl = _client(chain)
    lb = run(cl.verify_light_block_at_height(16))
    assert lb.height() == 16
    heights = cl.store.heights()
    assert 16 in heights and len(heights) > 2  # pivots were stored


def test_client_rejects_wrong_trust_hash():
    chain = LightChain(5)
    cl = Client(CHAIN_ID,
                TrustOptions(period_ns=HOUR, height=1, hash=b"\xab" * 32),
                chain.provider(), [], LightStore(MemDB()),
                now_fn=lambda: NOW)
    with pytest.raises(Exception, match="hash mismatch"):
        run(cl.initialize())


def test_client_detects_witness_divergence():
    """A witness serving an unprovable forgery is dropped (it cannot
    verify its header from any common block); a provable fork raises
    DivergenceError — the full flow lives in test_light_attack.py."""
    chain = LightChain(8)
    honest = chain.provider()
    lying = chain.provider(tamper_height=8)
    cl = _client(chain, witnesses=[honest, lying])
    lb = run(cl.verify_light_block_at_height(8))
    assert lb.height() == 8
    assert len(cl.witnesses) == 1  # liar removed, honest witness kept


def test_client_update_to_latest():
    chain = LightChain(12)
    cl = _client(chain)
    lb = run(cl.update())
    assert lb is not None and lb.height() == 12
    assert run(cl.update()) is None  # already at head


def test_light_client_against_live_node():
    async def go():
        from helpers import make_genesis
        from p2p_harness import P2PNode

        gdoc, pvs = make_genesis(1)
        node = P2PNode(gdoc, pvs[0], "full")
        await node.start()
        try:
            await node.cs.wait_for_height(5, timeout=60)
            prov = BlockStoreProvider(node.block_store,
                                      node.cs.block_exec.store)
            trusted = await prov.light_block(1)
            cl = Client(
                gdoc.chain_id,
                TrustOptions(period_ns=HOUR, height=1,
                             hash=trusted.hash()),
                prov, [prov], LightStore(MemDB()),
                # the test harness runs its chain clock ahead of the
                # wall clock (future genesis, see helpers.GENESIS_TIME)
                now_fn=lambda: gdoc.genesis_time + HOUR // 2,
            )
            lb = await cl.verify_light_block_at_height(4)
            assert lb.height() == 4
            assert lb.hash() == \
                node.block_store.load_block_meta(4).block_id.hash
        finally:
            await node.stop()

    run(go())


def test_backwards_verification():
    """Requesting a height BELOW THE FIRST trusted block walks the
    hash chain down from it (reference client.go:905 backwards,
    verifier.go:196 VerifyBackwards); a height between the first and
    the last trusted block is verified by signature
    (tests/test_light_between.py)."""
    import dataclasses

    from tendermint_tpu.light.verifier import verify_backwards

    chain = LightChain(8)
    cl = _client(chain, trust_height=8)
    run(cl.initialize())
    assert cl.store.heights() == [8]  # the root is the first trusted
    lb3 = run(cl.verify_light_block_at_height(3))
    assert lb3.height() == 3
    assert lb3.hash() == chain.blocks[3].hash()
    # interim headers are NOT persisted (reference client.go:
    # "Intermediate headers are not saved to database") — their commit
    # signatures were never verified; only the requested target is.
    for h in range(4, 8):
        assert cl.store.get(h) is None
    assert cl.store.get(3) is not None

    # unit: a forged interim header breaks the hash link
    good = chain.blocks[5].signed_header.header
    trusted = chain.blocks[6].signed_header.header
    verify_backwards(good, trusted)
    forged = dataclasses.replace(good, app_hash=b"\xee" * 32)
    with pytest.raises(LightClientError):
        verify_backwards(forged, trusted)
    # and non-decreasing time is rejected
    late = dataclasses.replace(good, time=trusted.time + 1)
    with pytest.raises(LightClientError):
        verify_backwards(late, trusted)


def test_backwards_rejects_tampering_primary():
    """A primary serving a forged interim header during the walk-down
    fails verification instead of polluting the store."""
    chain = LightChain(8)
    cl = _client(chain, trust_height=8,
                 primary=chain.provider(tamper_height=5))
    run(cl.initialize())
    with pytest.raises(LightClientError, match="backwards"):
        run(cl.verify_light_block_at_height(3))
    assert cl.store.get(5) is None and cl.store.get(3) is None


def test_dead_primary_promotes_witness():
    """reference client.go:975 lightBlockFromPrimary /
    replacePrimaryProvider: a primary failing with a transport error
    is replaced by the first witness and verification proceeds;
    BlockNotFoundError does NOT burn a witness (it is the normal
    height-not-committed-yet signal)."""
    from tendermint_tpu.light.provider import (
        BlockNotFoundError, Provider, ProviderError)

    chain = LightChain(8)

    class DeadPrimary(Provider):
        async def light_block(self, height):
            raise ProviderError("connection refused")

        def __repr__(self):
            return "DeadPrimary"

    good = chain.provider()
    dead = DeadPrimary()
    cl = _client(chain, primary=dead, witnesses=[good])
    lb = run(cl.verify_light_block_at_height(5))
    assert lb.height() == 5
    # ROTATED, not consumed: the dead primary is demoted to the
    # witness list (transient blips must not shrink the witness set)
    assert cl.primary is good and cl.witnesses == [dead]

    # not-found propagates without provider churn
    cl2 = _client(chain, witnesses=[chain.provider()])
    with pytest.raises(BlockNotFoundError):
        run(cl2.verify_light_block_at_height(999))
    assert len(cl2.witnesses) == 1

    # all providers dead -> the transport error surfaces
    cl3 = _client(chain, primary=DeadPrimary(), witnesses=[DeadPrimary()])
    with pytest.raises(ProviderError):
        run(cl3.verify_light_block_at_height(5))


def test_store_latest_height_single_scan():
    """LightStore scans the prefix ONCE, then answers from its index
    of heights, which saves, deletes and prunes keep in step. The light client calls latest() on every verify request,
    so this scan was per-request cost."""
    chain = LightChain(8)
    inner = MemDB()
    scans = []

    class CountingDB:
        def set(self, k, v):
            inner.set(k, v)

        def get(self, k):
            return inner.get(k)

        def delete(self, k):
            inner.delete(k)

        def iterate_prefix(self, prefix):
            scans.append(prefix)
            return inner.iterate_prefix(prefix)

    store = LightStore(CountingDB())
    for h in (1, 3, 5):
        store.save(chain.blocks[h])
    assert store.latest_height() == 5
    n_scans = len(scans)
    assert n_scans == 1
    # repeat reads and interleaved saves: zero further scans
    assert store.latest_height() == 5
    store.save(chain.blocks[7])
    assert store.latest_height() == 7
    store.save(chain.blocks[2])  # below the max: cache unchanged
    assert store.latest_height() == 7
    assert len(scans) == n_scans
    # deleting a NON-max height keeps the cache...
    store.delete(2)
    assert store.latest_height() == 7
    assert len(scans) == n_scans
    # ...and so does deleting the max: the index of heights is kept
    # in step, the one scan stays the only one
    store.delete(7)
    assert store.latest_height() == 5
    assert len(scans) == n_scans
    # the other questions a request asks come from the same index
    assert store.lowest_height() == 1
    assert store.height_before(5) == 3 and store.height_before(1) == 0
    assert store.light_block_before(4).height() == 3
    assert store.light_block_before(1) is None
    store.prune(1)
    assert store.heights() == [5]
    assert store.latest_height() == 5
    assert len(scans) == n_scans
    # full prune empties the store: the cache must not serve a ghost
    store.prune(0)
    assert store.latest_height() == 0
    assert store.latest() is None


def test_backwards_cache_and_trusted_anchor():
    """The backwards-walk linkage cache serves a walk that is made
    again without refetching (a walk starts at the FIRST trusted
    block, so only a walk that did not reach its end is made again),
    and anchor selection stays on TRUSTED blocks: a cached interim
    with an older timestamp must not fail the trusting-period check
    while a valid trusted anchor exists."""
    from tendermint_tpu.light.provider import ProviderError

    chain = LightChain(30)
    fetches = []
    down = {15}   # heights the primary fails to hand over, once

    base = chain.provider()

    class Counting(Provider):
        async def light_block(self, height):
            fetches.append(height)
            if height in down:
                down.discard(height)
                raise ProviderError(f"no answer for {height}")
            return await base.light_block(height)

    cl = _client(chain, trust_height=30, primary=Counting())
    run(cl.initialize())                      # first trusted block: 30
    with pytest.raises(ProviderError):
        run(cl.verify_light_block_at_height(10))  # walks 29..16, dies
    assert fetches[-1] == 15 and cl.store.heights() == [30]
    fetches.clear()
    # the same walk again: the linked range comes from the cache
    lb = run(cl.verify_light_block_at_height(10))
    assert lb.height() == 10
    assert fetches == list(range(15, 9, -1)), \
        f"cached part of the walk refetched: {fetches}"
    # anchor selection ignores cache entries: a cached interim with
    # an older header time sits closest above the target, the trust
    # period covers only the head — the walk must anchor on the
    # trusted head (and may still USE the cached link), not fail the
    # period check on the interim
    cl2 = _client(chain, trust_height=30, primary=Counting())
    run(cl2.initialize())
    cl2._interim_cache[29] = chain.blocks[29]
    # period covers h30 (time T0+30, now T0+100) but not h29
    cl2.trust_options.period_ns = 70 * 1_000_000_000 + 500_000_000
    fetches.clear()
    lb = run(cl2.verify_light_block_at_height(15))
    assert lb.height() == 15
    assert 29 not in fetches, "cached link for h29 was refetched"


@pytest.mark.parametrize("target,base", [(10, 1), (25, 20), (21, 20)])
def test_between_first_and_last_trusted_is_verified_by_signature(
        target, base):
    """reference client.go verifyLightBlock: a height between the first
    and the last trusted block is verified FORWARDS, by signature, from
    the closest trusted block below it — ONE fetch, no hash walk down
    from the head."""
    chain = LightChain(30)
    fetches = []
    inner = chain.provider()

    class Counting(Provider):
        async def light_block(self, height):
            fetches.append(height)
            return await inner.light_block(height)

    cl = _client(chain, primary=Counting())
    run(cl.verify_light_block_at_height(30))
    if base != 1:
        run(cl.verify_light_block_at_height(base))
    assert cl.store.height_before(target) == base
    assert cl.trusted_base(target).height() == base
    assert cl.trusted_base(31).height() == 30      # above: the latest
    fetches.clear()
    lb = run(cl.verify_light_block_at_height(target))
    assert lb.hash() == chain.blocks[target].hash()
    assert fetches == [target]
    assert cl.store.get(target) is not None
    assert cl._interim_cache == {}                 # nobody walked
    # a forged block there dies on its signatures, as above the head
    cl2 = _client(chain, primary=chain.provider(tamper_height=target))
    run(cl2.verify_light_block_at_height(30))
    with pytest.raises((LightClientError, ValueError)):
        run(cl2.verify_light_block_at_height(target))
    assert cl2.store.get(target) is None
