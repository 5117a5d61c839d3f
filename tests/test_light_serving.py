"""Light-client serving plane (light/serving.py): request coalescing,
the trusting-period-aware verified-header cache, batched skipping
verification through the shared collector, shed-newest overload
protection with 429s at the proxy, the serving pool, and the /status
`light` check. ISSUE 7 acceptance lives in
test_acceptance_coalescing_64_requests and
test_flood_dies_at_the_plane."""

import asyncio

import numpy as np
import pytest

from tendermint_tpu.config import LightConfig
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.libs import failpoints
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.metrics import light_metrics
from tendermint_tpu.light import (
    Client, LightServingShedError, LightStore, ServingPlane,
    ServingPool, TrustOptions, VerifiedHeaderCache,
)
from tendermint_tpu.light.errors import DivergenceError
from tendermint_tpu.light.proxy import LightProxy
from tendermint_tpu.light.serving import LightVerifyCollector
from tendermint_tpu.rpc.jsonrpc import HTTPClient, RPCError
from tendermint_tpu.types.validator_set import VerificationError

from helpers import CHAIN_ID
from test_light import HOUR, NOW, LightChain, _client


def run(coro):
    return asyncio.run(coro)


def _plane(chain, cfg=None, **client_kw) -> ServingPlane:
    plane = ServingPlane(_client(chain, **client_kw),
                         cfg or LightConfig(flush_ms=5.0))
    # host backend: deterministic launch counts without a kernel
    # compile (the device path is exercised by the faked-kernel tests)
    plane.collector.device_threshold = 10**9
    return plane


def _launches():
    met = light_metrics()
    return sum(met.verify_launches.value(backend=b)
               for b in ("device", "host", "host_recheck"))


def _corrupt_commit(lb):
    """Same block, every commit signature bit-flipped: structurally
    valid (block_id untouched), cryptographically dead."""
    import dataclasses

    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import Commit, CommitSig

    commit = lb.signed_header.commit
    sigs = [CommitSig(cs.block_id_flag, cs.validator_address,
                      cs.timestamp,
                      bytes(64) if cs.signature else cs.signature)
            for cs in commit.signatures]
    forged = Commit(commit.height, commit.round, commit.block_id, sigs)
    return LightBlock(SignedHeader(lb.signed_header.header, forged),
                      lb.validator_set)


# --- verified-header cache ----------------------------------------------


def test_cache_lru_and_trusting_period():
    chain = LightChain(6)
    cache = VerifiedHeaderCache(max_entries=3, period_ns=HOUR)
    for h in (1, 2, 3):
        cache.put(chain.blocks[h], NOW)
    assert cache.get(1, NOW) is chain.blocks[1]  # 1 now most-recent
    cache.put(chain.blocks[4], NOW)              # evicts LRU (2)
    assert cache.get(2, NOW) is None
    assert cache.get(1, NOW) is not None
    # trusting-period expiry: the entry is evicted on read the moment
    # its header time leaves the period — a block outside its period
    # must never be served as trusted
    t3 = chain.blocks[3].time()
    assert cache.get(3, t3 + HOUR - 1) is not None
    assert cache.get(3, t3 + HOUR) is None
    assert len(cache) == 2
    # and an already-expired block is never cached at all
    cache.put(chain.blocks[5], chain.blocks[5].time() + HOUR)
    assert cache.get(5, NOW) is None


# --- coalescing ---------------------------------------------------------


def test_singleflight_coalesces_same_height():
    """Concurrent requests for ONE height pay one verification: one
    primary fetch, one launch, N-1 coalesce counts."""
    chain = LightChain(8)
    fetches = []
    base = chain.provider()

    class Counting(type(base)):
        async def light_block(self, height):
            fetches.append(height)
            return await base.light_block(height)

    async def go():
        plane = _plane(chain, primary=Counting())
        await plane.client.initialize()
        fetches.clear()
        before = _launches()
        res = await asyncio.gather(*(plane.get_verified(8)
                                     for _ in range(16)))
        assert all(lb.hash() == chain.blocks[8].hash() for lb in res)
        assert fetches == [8]
        assert _launches() - before == 1
        assert plane.coalesced == 15
        plane.close()

    run(go())


def test_acceptance_coalescing_64_requests():
    """ISSUE 7 acceptance: ≥64 concurrent requests over ≤8 distinct
    heights through the plane — verify launches ≤ heights (not
    requests), cache hits > 0 on the second wave, and mean batch
    lanes per launch > 1 on the bisection path."""
    chain = LightChain(16)
    heights = list(range(9, 17))  # 8 distinct

    async def go():
        plane = _plane(chain)
        met = light_metrics()
        before = _launches()
        s0 = met.batch_lanes._series.get(())
        count0 = sum(s0.counts) if s0 else 0
        sum0 = s0.sum if s0 else 0.0

        # wave 1: 64 concurrent requests, 8 distinct heights
        res = await asyncio.gather(
            *(plane.get_verified(heights[i % 8]) for i in range(64)))
        for i, lb in enumerate(res):
            assert lb.hash() == chain.blocks[heights[i % 8]].hash()
        launches = _launches() - before
        assert launches <= len(heights), (
            f"{launches} launches for {len(heights)} heights")

        # mean lanes per launch: every bisection step contributes a
        # >1/3-power commit check of several lanes, and independent
        # requests coalesce — far more than one lane per launch
        s1 = met.batch_lanes._series.get(())
        lanes = s1.sum - sum0
        n_launches = sum(s1.counts) - count0
        assert n_launches == launches
        assert lanes / n_launches > 1, (
            f"mean lanes/launch {lanes / n_launches}")

        # wave 2: the cache answers
        hits0 = plane.cache_hits
        res2 = await asyncio.gather(*(plane.get_verified(h)
                                      for h in heights))
        assert [lb.height() for lb in res2] == heights
        assert plane.cache_hits - hits0 == len(heights)
        assert _launches() - before == launches  # no new launches
        plane.close()

    run(go())


def test_bisection_parity_with_client():
    """Rotating valset forces bisection: the plane's batched skipping
    verify must land exactly where the serial client lands — same
    target, pivots persisted to the trusted store — while coalescing
    the per-pivot commit checks into fewer launches."""
    make = lambda: LightChain(16, valset_for=lambda h: tuple(
        range(h, h + 4)))
    chain = make()

    async def go():
        cl = _client(chain)
        serial = await cl.verify_light_block_at_height(16)

        plane = _plane(chain)
        before = _launches()
        lb = await plane.get_verified(16)
        assert lb.hash() == serial.hash()
        plane_heights = set(plane.client.store.heights())
        assert set(cl.store.heights()) == plane_heights
        assert len(plane_heights) > 2  # pivots were stored
        # every pivot step is TWO commit checks; coalescing must beat
        # one launch per check
        checks = 2 * (len(plane_heights) - 1)
        assert _launches() - before < checks
        plane.close()

    run(go())


def test_backwards_and_latest_through_plane():
    chain = LightChain(12)

    async def go():
        plane = _plane(chain)
        lb = await plane.get_verified(0)     # latest
        assert lb.height() == 12
        # between the first (1) and the last (12) trusted block: by
        # signature from the closest one below, never a hash walk
        before = _launches()
        lb3 = await plane.get_verified(3)
        assert lb3.hash() == chain.blocks[3].hash()
        assert _launches() > before and plane.hash_walks == 0
        assert plane.steps == 2
        # latest again: served from the trusted store, no re-verify
        before = _launches()
        lb0 = await plane.get_verified(0)
        assert lb0.height() == 12 and _launches() == before
        plane.close()

    run(go())


def test_store_resident_height_serves_despite_saturation():
    """A saturated plane still serves heights that sit verified and
    in-period in the trusted store (a READ, probed before the
    admission gate) — while a below-head height that would need a
    backwards walk (new primary fetches) sheds like any other new
    work. 'Only requests that would start NEW verification work
    shed' is the documented queue contract."""
    chain = LightChain(16)

    async def go():
        # pending_max=4: two non-adjacent pairs fill the backlog (the
        # both-or-neither pair admission needs 2 free slots per
        # skipping verify)
        plane = _plane(chain, cfg=LightConfig(flush_ms=1.0,
                                              pending_max=4))
        await plane.get_verified(10)   # store: {1, 10}
        plane.cache.clear()            # store-only: the LRU is cold
        failpoints.arm("light.verify", "delay", delay_ms=400)
        try:
            flood = [asyncio.ensure_future(plane.get_verified(h))
                     for h in range(12, 17)]
            for _ in range(400):
                if plane.collector.saturated():
                    break
                await asyncio.sleep(0.005)
            assert plane.collector.saturated()
            lb10 = await plane.get_verified(10)   # store probe
            assert lb10.hash() == chain.blocks[10].hash()
            with pytest.raises(LightServingShedError):
                await plane.get_verified(5)       # backwards walk
            await asyncio.gather(*flood, return_exceptions=True)
        finally:
            failpoints.reset()
        plane.close()

    run(go())


def test_concurrent_lower_height_not_refused_by_advancing_head():
    """The trusted head a verification runs from is captured BEFORE
    the primary fetch (the serial client's order): while a request
    for height 5 awaits its fetch, a concurrent request verifies
    height 10 and advances store.latest() — re-reading the head after
    the await would make _common_checks refuse height 5 as 'not above
    trusted'. The mixed-height concurrent workload is exactly what
    the plane serves."""
    chain = LightChain(10)
    base = chain.provider()

    class Slow5(type(base)):
        def __init__(self):
            self.gate = None

        async def light_block(self, height):
            if height == 5 and self.gate is not None:
                await self.gate.wait()
            return await base.light_block(height)

    async def go():
        prov = Slow5()
        prov.gate = asyncio.Event()
        plane = _plane(chain, primary=prov)
        await plane.client.initialize()
        t5 = asyncio.ensure_future(plane.get_verified(5))
        await asyncio.sleep(0.01)      # t5 parked on the fetch gate
        lb10 = await plane.get_verified(10)
        assert lb10.height() == 10
        assert plane.client.store.latest_height() == 10
        prov.gate.set()                # head has advanced past 5
        lb5 = await t5
        assert lb5.hash() == chain.blocks[5].hash()
        plane.close()

    run(go())


def test_expired_store_never_served_trusted():
    """A stored block whose header time has left the trusting period
    is NOT served on the strength of the old verification alone (the
    serial client returns stored blocks unconditionally; the plane
    serves untrusted public clients and enforces the cache invariant
    on the store path too): at the trusted head it raises
    OutsideTrustingPeriodError, below the head the backwards walk
    re-proves it by hash linkage from an in-period anchor — with zero
    signature launches."""
    from tendermint_tpu.light.errors import OutsideTrustingPeriodError

    chain = LightChain(8)

    async def go():
        plane = _plane(chain)
        await plane.get_verified(8)          # store: {1, 8}
        await plane.get_verified(5)          # by signature from 1: +{5}
        # clock jump: 5 leaves its period, the head (8) stays inside
        t5 = chain.blocks[5].time()
        plane.client.now_fn = lambda: t5 + HOUR + 1
        plane.cache.clear()
        before = _launches()
        lb5 = await plane.get_verified(5)    # re-proved via linkage
        assert lb5.hash() == chain.blocks[5].hash()
        assert _launches() == before
        # the head itself expires: nothing to anchor on — refuse
        plane.client.now_fn = lambda: chain.blocks[8].time() + HOUR
        plane.cache.clear()
        with pytest.raises(OutsideTrustingPeriodError):
            await plane.get_verified(8)
        plane.close()

    run(go())


# --- per-plan verdict isolation ----------------------------------------


def test_collector_scatters_verdicts_per_plan():
    """One coalesced launch carrying a good plan and a forged-commit
    plan: the bad plan alone fails (slots named), the good plan's
    verdict is untouched by its batchmate."""
    chain = LightChain(4)
    good = chain.blocks[3]
    bad = _corrupt_commit(chain.blocks[4])

    async def go():
        coll = LightVerifyCollector(batch_max=10**6, flush_ms=20.0,
                                    pending_max=64,
                                    device_threshold=10**9)
        sh_g, sh_b = good.signed_header, bad.signed_header
        plan_g = good.validator_set.plan_commit_light(
            CHAIN_ID, sh_g.commit.block_id, sh_g.header.height,
            sh_g.commit)
        plan_b = bad.validator_set.plan_commit_light(
            CHAIN_ID, sh_b.commit.block_id, sh_b.header.height,
            sh_b.commit)
        res = await asyncio.gather(coll.check(plan_g),
                                   coll.check(plan_b),
                                   return_exceptions=True)
        assert res[0] is None
        assert isinstance(res[1], VerificationError)
        assert "invalid signature" in str(res[1])
        coll.close()

    run(go())


def test_forged_target_rejected_by_plane():
    chain = LightChain(8)

    async def go():
        plane = _plane(chain, primary=chain.provider(tamper_height=8))
        from tendermint_tpu.light.errors import LightClientError

        # structural forgery fails validate_basic (ValueError), same
        # as the serial client path; nothing lands in store or cache
        with pytest.raises((LightClientError, ValueError)):
            await plane.get_verified(8)
        assert plane.client.store.get(8) is None
        assert plane.cache.get(8, NOW) is None
        plane.close()

    run(go())


# --- overload: shed-newest at the plane --------------------------------


def test_flood_dies_at_the_plane():
    """ISSUE 7 acceptance: with light.verify delayed, a distinct-
    height request flood sheds-newest with 429-shaped errors, the
    pending-verify depth never exceeds its bound, the /status `light`
    body reads degraded while saturated, and a fresh request verifies
    once the stall clears."""
    chain = LightChain(10)

    async def go():
        plane = _plane(chain, cfg=LightConfig(flush_ms=1.0,
                                              pending_max=2))
        await plane.client.initialize()
        failpoints.arm("light.verify", "delay", delay_ms=600)
        try:
            tasks = [asyncio.ensure_future(plane.get_verified(h))
                     for h in range(5, 11)]
            max_depth = 0
            degraded_seen = False
            while not all(t.done() for t in tasks):
                depth = plane.collector.depth()
                max_depth = max(max_depth, depth)
                if depth >= plane.collector.pending_max:
                    degraded_seen |= (
                        plane.status_check()["status"] == "degraded")
                await asyncio.sleep(0.01)
            res = await asyncio.gather(*tasks, return_exceptions=True)
        finally:
            failpoints.reset()
        shed = [r for r in res
                if isinstance(r, LightServingShedError)]
        served = [r for r in res if not isinstance(r, BaseException)]
        assert shed, "no requests were shed"
        assert served, "every request was shed"
        assert max_depth <= plane.collector.pending_max
        assert degraded_seen, "/status never reported degraded"
        assert plane.sheds["queue_full"] == len(shed)
        # stall cleared: the plane serves again
        lb = await plane.get_verified(7)
        assert lb.hash() == chain.blocks[7].hash()
        plane.close()

    run(go())


def test_failpoint_error_degrades_to_host():
    """light.verify `error` (failed launch) degrades to the host
    oracle: requests still verify, nothing is rejected."""
    chain = LightChain(6)

    async def go():
        plane = _plane(chain)
        met = light_metrics()
        host0 = met.verify_launches.value(backend="host")
        failpoints.arm("light.verify", "error")
        try:
            lb = await plane.get_verified(6)
        finally:
            failpoints.reset()
        assert lb.hash() == chain.blocks[6].hash()
        assert met.verify_launches.value(backend="host") > host0
        plane.close()

    run(go())


# --- device path (kernel faked): sentinel lane + breaker ----------------


def test_device_sentinel_mismatch_reverifies_on_host(monkeypatch):
    """A device batch whose known-answer sentinel lane reads invalid
    (NaN-ing kernel) re-verifies on host: valid headers are SERVED,
    not failed on wrong verdicts, and the breaker opens."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    monkeypatch.setattr(
        tpu_verify, "verify_batch",
        lambda pubs, msgs, sigs, shapes=None: np.zeros(len(pubs), bool))
    cbatch.reset_breakers()
    chain = LightChain(6)

    async def go():
        plane = ServingPlane(_client(chain), LightConfig(flush_ms=5.0))
        plane.collector.device_threshold = 1  # force the device path
        met = light_metrics()
        recheck0 = met.verify_launches.value(backend="host_recheck")
        lb = await plane.get_verified(6)
        assert lb.hash() == chain.blocks[6].hash()
        assert met.verify_launches.value(backend="host_recheck") \
            > recheck0
        assert not cbatch.device_available("ed25519")
        plane.close()

    try:
        run(go())
    finally:
        cbatch.reset_breakers()


def test_device_verdicts_trusted_when_sentinel_verifies(monkeypatch):
    """Sentinel valid → the device verdicts are trusted as-is: a
    forged commit dies on the device verdict with no host re-check."""
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    def oracle_device(pubs, msgs, sigs, shapes=None):
        return np.array(
            [Ed25519PubKey(p).verify_signature(m, s)
             for p, m, s in zip(pubs, msgs, sigs)], bool)

    monkeypatch.setattr(tpu_verify, "verify_batch", oracle_device)
    cbatch.reset_breakers()
    chain = LightChain(6)
    bad = _corrupt_commit(chain.blocks[5])

    async def go():
        coll = LightVerifyCollector(batch_max=10**6, flush_ms=10.0,
                                    pending_max=64,
                                    device_threshold=1)
        met = light_metrics()
        dev0 = met.verify_launches.value(backend="device")
        recheck0 = met.verify_launches.value(backend="host_recheck")
        sh = bad.signed_header
        plan = bad.validator_set.plan_commit_light(
            CHAIN_ID, sh.commit.block_id, sh.header.height, sh.commit)
        with pytest.raises(VerificationError):
            await coll.check(plan)
        assert met.verify_launches.value(backend="device") == dev0 + 1
        assert met.verify_launches.value(backend="host_recheck") \
            == recheck0
        assert cbatch.device_available("ed25519")
        coll.close()

    run(go())


def test_open_breaker_routes_to_host(monkeypatch):
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    def must_not_launch(*a, **kw):
        raise AssertionError("device launched through an open breaker")

    monkeypatch.setattr(tpu_verify, "verify_batch", must_not_launch)
    cbatch.breaker("ed25519").record_failure()
    chain = LightChain(4)

    async def go():
        plane = ServingPlane(_client(chain), LightConfig(flush_ms=5.0))
        plane.collector.device_threshold = 1
        lb = await plane.get_verified(4)
        assert lb.hash() == chain.blocks[4].hash()
        plane.close()

    try:
        run(go())
    finally:
        cbatch.reset_breakers()


# --- divergence safety --------------------------------------------------


def test_proven_fork_clears_the_cache():
    """A DivergenceError out of witness cross-checking purges the
    plane's LRU — later requests must not be served the (possibly
    forged) chain from memory after the store was purged."""
    chain = LightChain(8)

    async def go():
        plane = _plane(chain)
        await plane.get_verified(5)
        assert len(plane.cache) > 0

        async def proven_fork(verified, now_ns):
            raise DivergenceError(0, chain.blocks[8], chain.blocks[8])

        plane.client._detect_divergence = proven_fork
        with pytest.raises(DivergenceError):
            await plane.get_verified(8)
        assert len(plane.cache) == 0
        plane.close()

    run(go())


# --- proxy + pool -------------------------------------------------------


def test_proxy_serves_through_plane_and_maps_shed_to_429():
    chain = LightChain(8)

    async def go():
        plane = _plane(chain, cfg=LightConfig(flush_ms=2.0,
                                              pending_max=2))
        proxy = LightProxy(plane.client, plane=plane)
        port = await proxy.listen("127.0.0.1", 0)
        try:
            http = HTTPClient("127.0.0.1", port)
            cm = await http.call("commit", height=6)
            assert bytes.fromhex(
                cm["signed_header"]["commit"]["block_id"]["hash"]) \
                == chain.blocks[6].hash()
            # a shed surfaces as a 429-coded RPC error, not a -32603
            failpoints.arm("light.verify", "delay", delay_ms=500)
            try:
                results = await asyncio.gather(
                    *(http.call("commit", height=h)
                      for h in range(2, 9)),
                    return_exceptions=True)
            finally:
                failpoints.reset()
            sheds = [r for r in results
                     if isinstance(r, RPCError) and r.code == 429]
            assert sheds, "no 429s surfaced at the proxy"
            for s in sheds:
                assert "overloaded" in s.message
        finally:
            proxy.close()
            plane.close()

    run(go())


def test_serving_pool_shares_one_plane():
    """Two proxy workers, one plane: requests through BOTH ports
    coalesce into the shared collector — launches bounded by distinct
    heights, not by (workers x requests)."""
    chain = LightChain(8)

    async def go():
        cl = _client(chain)
        pool = ServingPool(cl, workers=2,
                           config=LightConfig(flush_ms=5.0))
        pool.plane.collector.device_threshold = 10**9
        ports = await pool.listen("127.0.0.1")
        assert len(ports) == 2
        try:
            clients = [HTTPClient("127.0.0.1", p) for p in ports]
            before = _launches()
            res = await asyncio.gather(
                *(clients[i % 2].call("header", height=6 + (i % 3))
                  for i in range(18)))
            for i, hd in enumerate(res):
                assert int(hd["header"]["height"]) == 6 + (i % 3)
            assert _launches() - before <= 3
        finally:
            pool.close()

    run(go())


def test_pool_worker_count_from_config():
    chain = LightChain(3)

    async def go():
        pool = ServingPool(_client(chain),
                           config=LightConfig(workers=3))
        assert len(pool.proxies) == 3
        pool.close()
        with pytest.raises(ValueError, match="at least one"):
            ServingPool(_client(chain), workers=0)

    run(go())


# --- /status + config ---------------------------------------------------


def test_status_light_check_registration():
    from tendermint_tpu.libs.debugsrv import DebugServer
    from tendermint_tpu.light.serving import active_plane

    chain = LightChain(4)

    async def go():
        plane = _plane(chain)
        assert active_plane() is plane
        await plane.get_verified(4)
        srv = DebugServer()
        st = srv.health.status()
        assert st["checks"]["light"]["status"] == "ok"
        assert st["checks"]["light"]["trusted_height"] == 4
        assert st["checks"]["light"]["requests"] == 1
        plane.close()
        assert active_plane() is None
        assert "light" not in srv.health.status()["checks"]

    run(go())


def test_light_config_validation():
    from tendermint_tpu.config import Config

    cfg = Config()
    cfg.light.pending_max = 0
    with pytest.raises(ValueError, match="light.pending_max"):
        cfg.validate_basic()
    # floor is 2, not 1: a non-adjacent verification parks TWO
    # concurrent commit checks — pending_max=1 would deterministically
    # shed every skipping verify on an idle plane
    cfg.light.pending_max = 1
    with pytest.raises(ValueError, match="light.pending_max"):
        cfg.validate_basic()
    cfg.light.pending_max = 8
    cfg.light.flush_ms = -1.0
    with pytest.raises(ValueError, match="light.flush_ms"):
        cfg.validate_basic()
    cfg.light.flush_ms = 2.0
    cfg.validate_basic()
    # config file round trip carries the [light] section
    import os
    import tempfile

    cfg.light.pending_max = 99
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.toml")
        cfg.save(path)
        loaded = Config.load(path)
        assert loaded.light.pending_max == 99
        assert loaded.light.workers == cfg.light.workers


def test_backpressure_lint_covers_light_queue():
    import sys as _sys

    _sys.path.insert(0, "tools")
    from check_backpressure import collect_problems

    assert collect_problems() == []


def test_e2e_manifest_light_proxy_op():
    from tendermint_tpu.e2e.manifest import Manifest

    m = Manifest.from_dict({
        "nodes": 2, "wait_height": 8,
        "perturbations": [
            {"node": 0, "op": "light_proxy", "at_height": 5,
             "duration": 2.0},
        ],
    })
    assert m.perturbations[0].op == "light_proxy"
    with pytest.raises(ValueError, match="at_height must be >= 4"):
        Manifest.from_dict({
            "nodes": 2, "wait_height": 8,
            "perturbations": [
                {"node": 0, "op": "light_proxy", "at_height": 2},
            ],
        })


# --- the plane as `cmd light` runs it -----------------------------------


def test_a_cut_leaves_the_sentinels_lane_free():
    """A device launch holds at most the widest stated lane count, the
    sentinel's lane counted in: cuts of at most batch_max - 1 signature
    lanes, and a plan wider than that alone, in pieces."""
    seen = []

    class Spy(LightVerifyCollector):
        def _verify_triples(self, triples):
            seen.append(len(triples))
            return np.ones(len(triples), bool)

    chain = LightChain(6)

    def plan(h):
        lb = chain.blocks[h]
        sh = lb.signed_header
        return lb.validator_set.plan_commit_light(
            CHAIN_ID, sh.commit.block_id, sh.header.height, sh.commit)

    async def go():
        coll = Spy(batch_max=8, flush_ms=5.0, pending_max=64)
        assert coll.shapes.lanes == 8 and coll.batch_max == 7
        await asyncio.gather(*(coll.check(plan(h)) for h in range(1, 6)))
        coll.close()

    run(go())
    assert seen == [6, 6, 3]       # 3-lane plans: two a cut, never 9


@pytest.mark.parametrize("lanes,launches", [
    (3, [8]), (7, [8]), (8, [8, 8]), (20, [8, 8, 8])])
def test_wide_batches_split_onto_the_stated_shapes(monkeypatch, lanes,
                                                   launches):
    """Lanes past the shape go as further launches of the same shape,
    a sentinel each."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    got = []

    def fake(pubs, msgs, sigs, shapes=None):
        got.append(shapes.fit(len(pubs)))
        return np.ones(len(pubs), bool)

    monkeypatch.setattr(tpu_verify, "verify_batch", fake)
    cbatch.reset_breakers()
    chain = LightChain(2)
    pk = chain.blocks[1].validator_set.validators[0].pub_key
    coll = LightVerifyCollector(batch_max=8, device_threshold=1)
    try:
        out = coll._verify_triples([(pk, b"m", bytes(64))] * lanes)
    finally:
        coll.close()
        cbatch.reset_breakers()
    assert out.all() and [x for fit in got for x in fit] == launches


def test_resolved_since_counts_every_request_once():
    chain = LightChain(8)

    async def go():
        plane = _plane(chain)
        await asyncio.gather(plane.get_verified(5), plane.get_verified(5),
                             plane.get_verified(6))
        assert plane.resolved_since() == {
            "hits": 0, "coalesced": 1, "misses": 2}
        await plane.get_verified(5)                  # the LRU
        plane.cache.clear()
        await plane.get_verified(6)                  # the trusted store
        assert plane.resolved_since() == {
            "hits": 2, "coalesced": 0, "misses": 0}
        assert plane.resolved_since() == {
            "hits": 0, "coalesced": 0, "misses": 0}
        st = plane.status_check()
        assert st["steps"] == 2 and st["hash_walks"] == 0
        plane.close()

    run(go())


def test_follow_loop_outlives_a_saturated_plane(capsys):
    """`cmd light`'s follow loop (cmd.follow_light) polls
    plane.get_verified(0) beside the RPC callers. A flood of distinct
    heights saturates the collector, and the loop's poll is shed like
    any caller's: that tick is skipped and said so, the daemon lives,
    and the next tick verifies the newest header. A block the client
    refuses ends it, as an error of Client.update() did."""
    from tendermint_tpu import cmd

    chain = LightChain(16)

    async def go():
        plane = _plane(chain, cfg=LightConfig(flush_ms=1.0,
                                              pending_max=4))
        await plane.initialize()
        failpoints.arm("light.verify", "delay", delay_ms=300)
        try:
            flood = [asyncio.ensure_future(plane.get_verified(h))
                     for h in range(10, 15)]
            for _ in range(400):
                if plane.collector.saturated():
                    break
                await asyncio.sleep(0.005)
            assert plane.collector.saturated()
            shed_before = plane.sheds["queue_full"]
            # one tick against the saturated plane: shed, not raised
            await cmd.follow_light(plane, 1, 0.01, once=True)
            assert plane.sheds["queue_full"] == shed_before + 1
            loop = asyncio.ensure_future(
                cmd.follow_light(plane, 1, 0.01))
            await asyncio.gather(*flood, return_exceptions=True)
            for _ in range(400):
                if plane.client.store.latest_height() == 16:
                    break
                await asyncio.sleep(0.01)
            assert plane.client.store.latest_height() == 16
            assert not loop.done()
            loop.cancel()
        finally:
            failpoints.reset()
        # a refused block is not backpressure: it ends the loop
        plane.client.primary = chain.provider(tamper_height=16)
        plane.cache.clear()
        plane.client.store.delete(16)
        with pytest.raises(ValueError, match="different block"):
            await cmd.follow_light(plane, 1, 0.01, once=True)
        plane.close()

    run(go())
    out = capsys.readouterr().out
    assert "follow tick skipped" in out
    assert "verified height 16" in out


def test_start_light_pool_is_the_commands_stack(monkeypatch, tmp_path):
    """`cmd light --laddr` serves through cmd.start_light_pool: [crypto]
    applied, [light] workers over ONE plane, the shape loaded, the
    root pinned through the plane, then the ports; the store on the
    configured durable db."""
    from tendermint_tpu import cmd
    from tendermint_tpu.config import Config
    from tendermint_tpu.libs.db import MemDB as Mem, SqliteDB

    loaded = []
    monkeypatch.setattr(cbatch, "load_ed25519_programs",
                        lambda shapes: loaded.append(shapes.lanes) or 1)
    cfg = Config()
    assert isinstance(cmd.light_store_db(cfg, ""), Mem)
    db = cmd.light_store_db(cfg, str(tmp_path / "light.sqlite"))
    assert isinstance(db, SqliteDB)
    chain = LightChain(6)

    async def go():
        client = Client(
            CHAIN_ID, TrustOptions(period_ns=HOUR, height=1,
                                   hash=chain.blocks[1].hash()),
            chain.provider(), [], LightStore(db), now_fn=lambda: NOW)
        pool = await cmd.start_light_pool(cfg, client, "127.0.0.1", 0)
        try:
            assert loaded == [1024]
            assert len(pool.ports) == cfg.light.workers == 2
            assert all(p.plane is pool.plane for p in pool.proxies)
            assert client.store.heights() == [1]
            pool.plane.collector.device_threshold = 10**9
            res = await HTTPClient("127.0.0.1", pool.ports[1]).call(
                "commit", height=4)
            assert res["signed_header"]["header"]["height"] == "4"
        finally:
            pool.close()

    run(go())
    db.close()
