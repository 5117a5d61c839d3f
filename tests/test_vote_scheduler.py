"""Vote micro-batch scheduler edge cases (VERDICT r2 weak #6):
rejected lanes in a mixed batch, device-failure -> sync fallback,
duplicate suppression, and replay-mode bypass
(consensus/state.py _enqueue_vote/_vote_scheduler)."""

import asyncio

from tendermint_tpu.consensus import messages as m
from tendermint_tpu.types.vote import Vote, VoteType

from helpers import make_genesis
from test_consensus import Node


def run(coro):
    return asyncio.run(coro)


def _prevote(cs, gdoc, pvs, pv_idx, height=1, round_=0, block_hash=b""):
    """A signed prevote from pvs[pv_idx]; the validator INDEX is looked
    up in the node's valset (ordering is by address, not pv order).
    Returns (vote, index)."""
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    pv = pvs[pv_idx]
    addr = pv.get_pub_key().address()
    idx, _ = cs.rs.validators.get_by_address(addr)
    bid = BlockID(block_hash, PartSetHeader(1, b"\x07" * 32)) \
        if block_hash else None
    vote = Vote(
        type=VoteType.PREVOTE, height=height, round=round_,
        block_id=bid, timestamp=1_700_000_001_000_000_000,
        validator_address=addr,
        validator_index=idx,
    )
    pv.sign_vote(gdoc.chain_id, vote)
    return vote, idx


async def _wait_tallied(cs, val_idx, round_=0, timeout=10.0, want=True):
    for _ in range(int(timeout / 0.02)):
        pv_set = cs.rs.votes.prevotes(round_) if cs.rs.votes else None
        if pv_set is not None and \
                (pv_set.votes[val_idx] is not None) == want:
            return True
        await asyncio.sleep(0.02)
    pv_set = cs.rs.votes.prevotes(round_) if cs.rs.votes else None
    return pv_set is not None and (pv_set.votes[val_idx] is not None) == want


def test_mixed_batch_rejected_lane():
    """Valid and invalid signatures in ONE scheduler batch: the valid
    lanes tally, the corrupt lane is dropped, nothing raises."""
    async def go():
        gdoc, pvs = make_genesis(4)
        node = Node(gdoc, pvs[0])
        await node.start()
        try:
            v1, i1 = _prevote(node.cs, gdoc, pvs, 1)
            v2, i2 = _prevote(node.cs, gdoc, pvs, 2)
            v2.signature = b"\x13" * 64  # corrupt
            v3, i3 = _prevote(node.cs, gdoc, pvs, 3)
            for v in (v1, v2, v3):
                await node.cs.add_peer_msg(m.VoteMessage(v), "peerX")
            assert await _wait_tallied(node.cs, i1)
            assert await _wait_tallied(node.cs, i3)
            assert await _wait_tallied(node.cs, i2, want=False)
        finally:
            await node.stop()

    run(go())


def test_device_failure_falls_back_to_sync_path():
    """BatchVerifier exploding (device error) must not kill the
    scheduler or lose votes: the sync path re-verifies vote by vote."""
    async def go():
        gdoc, pvs = make_genesis(4)
        node = Node(gdoc, pvs[0])
        await node.start()
        from tendermint_tpu.crypto.batch import BatchVerifier

        orig = BatchVerifier.verify

        def boom(self):
            raise RuntimeError("synthetic device failure")

        BatchVerifier.verify = boom
        try:
            v1, i1 = _prevote(node.cs, gdoc, pvs, 1)
            v2, i2 = _prevote(node.cs, gdoc, pvs, 2)
            v2.signature = b"\x13" * 64  # still rejected on sync path
            await node.cs.add_peer_msg(m.VoteMessage(v1), "peerX")
            await node.cs.add_peer_msg(m.VoteMessage(v2), "peerX")
            assert await _wait_tallied(node.cs, i1)
            assert await _wait_tallied(node.cs, i2, want=False)
            # scheduler survived: a later (post-restore) vote verifies
            BatchVerifier.verify = orig
            v3, i3 = _prevote(node.cs, gdoc, pvs, 3)
            await node.cs.add_peer_msg(m.VoteMessage(v3), "peerX")
            assert await _wait_tallied(node.cs, i3)
        finally:
            BatchVerifier.verify = orig
            await node.stop()

    run(go())


def test_duplicate_suppression():
    """A gossip duplicate of an already-tallied vote never burns a
    device lane (is_duplicate short-circuit), and two copies in the
    SAME batch dedup at commit time."""
    async def go():
        gdoc, pvs = make_genesis(4)
        node = Node(gdoc, pvs[0])
        await node.start()
        try:
            v1, i1 = _prevote(node.cs, gdoc, pvs, 1)
            # same-vote twice in one window: one tally, no error
            await node.cs.add_peer_msg(m.VoteMessage(v1), "pA")
            await node.cs.add_peer_msg(m.VoteMessage(v1), "pB")
            assert await _wait_tallied(node.cs, i1)
            await asyncio.sleep(0.05)  # let the batch fully drain
            # re-gossip after commit: suppressed before the buffer
            assert node.cs._enqueue_vote(v1, "pC") is True
            assert node.cs._vote_buf == [], \
                "tallied duplicate still consumed a batch lane"
        finally:
            await node.stop()

    run(go())


def test_replay_mode_bypasses_scheduler():
    """WAL replay must verify votes synchronously (deterministic
    replay; no batching task is running yet)."""
    async def go():
        gdoc, pvs = make_genesis(4)
        node = Node(gdoc, pvs[0])
        await node.start()
        try:
            node.cs._replay_mode = True
            v1, i1 = _prevote(node.cs, gdoc, pvs, 1)
            await node.cs.add_peer_msg(m.VoteMessage(v1), "")
            assert await _wait_tallied(node.cs, i1)
            assert node.cs._vote_buf == [], \
                "replay-mode vote went through the async scheduler"
        finally:
            node.cs._replay_mode = False
            await node.stop()

    run(go())


def test_batch_verdicts_feed_trust_metric():
    """Verified lanes credit the sending peer, rejected lanes debit it
    and trigger enforcement — wired via cs.reporter_fn (behaviour.py)."""
    async def go():
        gdoc, pvs = make_genesis(4)
        node = Node(gdoc, pvs[0])
        await node.start()

        class FakeReporter:
            def __init__(self):
                self.observed = []
                self.enforced = []

            def observe(self, peer_id, good=0, bad=0):
                self.observed.append((peer_id, good, bad))

            async def enforce(self, peer_id, reason):
                self.enforced.append((peer_id, reason))

        rep = FakeReporter()
        node.cs.reporter_fn = lambda: rep
        try:
            v1, i1 = _prevote(node.cs, gdoc, pvs, 1)
            v2, i2 = _prevote(node.cs, gdoc, pvs, 2)
            v2.signature = b"\x13" * 64
            await node.cs.add_peer_msg(m.VoteMessage(v1), "goodpeer")
            await node.cs.add_peer_msg(m.VoteMessage(v2), "badpeer")
            assert await _wait_tallied(node.cs, i1)
            assert await _wait_tallied(node.cs, i2, want=False)
            for _ in range(100):
                if rep.enforced:
                    break
                await asyncio.sleep(0.02)
            goods = {p: g for p, g, b in rep.observed if g}
            bads = {p: b for p, g, b in rep.observed if b}
            assert goods.get("goodpeer", 0) >= 1
            assert bads.get("badpeer", 0) >= 1
            assert any(p == "badpeer" for p, _ in rep.enforced)
        finally:
            await node.stop()

    run(go())


def test_net_stays_live_under_persistent_device_failure():
    """VERDICT r3 weak #6 done-bar: with the device kernels
    PERMANENTLY raising (dead backend) and the device threshold
    forced to 1 so every batch tries the device, a 4-validator net
    keeps producing blocks: BatchVerifier degrades device -> host
    inside verify(), every call site (vote scheduler, commit verify,
    expanded valset) inherits it, and the degraded crypto runs off
    the event loop."""
    async def go():
        from tendermint_tpu.crypto import batch as B
        from tendermint_tpu.crypto.tpu import verify as tv

        from test_consensus import wire_network

        gdoc, pvs = make_genesis(4)
        nodes = [Node(gdoc, pv) for pv in pvs]
        for n in nodes:
            await n.start()

        def boom(*a, **k):
            raise RuntimeError("synthetic persistent device failure")

        orig_vb, orig_thr = tv.verify_batch, B._DEVICE_THRESHOLD
        tv.verify_batch = boom
        B._DEVICE_THRESHOLD = 1
        B.reset_breakers()
        # make the breaker cooldown expire constantly so the dead
        # device is PROBED during the run (worst case: failing
        # half-open probes interleaved with consensus), not just
        # skipped while open
        orig_cd = B.BREAKER_BASE_COOLDOWN_S
        B.BREAKER_BASE_COOLDOWN_S = 0.05
        try:
            wire_network(nodes)
            await asyncio.gather(*[
                n.cs.wait_for_height(3, timeout=60) for n in nodes
            ])
        finally:
            tv.verify_batch = orig_vb
            B._DEVICE_THRESHOLD = orig_thr
            B.BREAKER_BASE_COOLDOWN_S = orig_cd
            B.reset_breakers()
            for n in nodes:
                await n.stop()

    run(go())


def test_device_failure_cooldown_and_recovery():
    """A raising device opens its circuit breaker (host verdicts,
    correct), is not retried while the breaker is open, and is picked
    back up once the breaker closes — without a restart."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.tpu import verify as tv

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("dead device")

    orig = tv.verify_batch
    tv.verify_batch = boom
    B.reset_breakers()
    try:
        sk = Ed25519PrivKey.generate()
        msg, sig = b"m", None
        sig = sk.sign(msg)
        bv = B.BatchVerifier(use_device=True)
        bv.add(sk.pub_key(), msg, sig)
        ok, v = bv.verify()
        assert ok and list(v) == [True]  # host fallback, same verdict
        assert len(calls) == 1 and not B.device_available("ed25519")
        # open: production batches take the host path, no launches
        bv2 = B.BatchVerifier(use_device=True)
        bv2.add(sk.pub_key(), msg, sig)
        assert bv2.verify()[0]
        assert len(calls) == 1
        # breaker closed again (a successful probe would do this):
        # the device is retried without a restart
        B.reset_breakers()
        bv3 = B.BatchVerifier(use_device=True)
        bv3.add(sk.pub_key(), msg, sig)
        assert bv3.verify()[0]
        assert len(calls) == 2
    finally:
        tv.verify_batch = orig
        B.reset_breakers()
