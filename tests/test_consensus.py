"""Consensus state machine: single-validator block production, a
4-validator in-process network (the reference consensus/common_test.go
harness analogue), restart recovery, and handshake replay."""

import asyncio

import pytest

from tendermint_tpu.abci.client import ClientCreator
from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
from tendermint_tpu.config import fast_consensus_config
from tendermint_tpu.consensus import messages as m
from tendermint_tpu.consensus.replay import handshake_and_load_state
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.libs.db import FileDB, MemDB
from tendermint_tpu.proxy import AppConns
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.store import Store
from tendermint_tpu.store import BlockStore
from tendermint_tpu.types.events import EventBus

from helpers import deterministic_pv, make_genesis


class Node:
    """One in-process validator node (stores + app + consensus)."""

    def __init__(self, gdoc, pv, tmp_path=None, tag="",
                 speculation=False):
        self.gdoc = gdoc
        self.pv = pv
        self.speculation = speculation
        if tmp_path is not None:
            self.state_db = FileDB(str(tmp_path / f"state{tag}.db"))
            self.block_db = FileDB(str(tmp_path / f"blocks{tag}.db"))
            self.app_db = FileDB(str(tmp_path / f"app{tag}.db"))
            self.wal_path = str(tmp_path / f"wal{tag}")
        else:
            self.state_db = MemDB()
            self.block_db = MemDB()
            self.app_db = MemDB()
            self.wal_path = None
        self.cs = None
        self.conns = None

    async def start(self):
        self.app = PersistentKVStoreApp(self.app_db)
        self.conns = AppConns(ClientCreator(app=self.app))
        await self.conns.start()
        state_store = Store(self.state_db)
        block_store = BlockStore(self.block_db)
        state = await handshake_and_load_state(
            None, state_store, block_store, self.gdoc, self.conns,
        )
        self.event_bus = EventBus()
        spec_plane = None
        if self.speculation:
            from tendermint_tpu.consensus.speculation import (
                SpeculationPlane,
            )

            spec_plane = SpeculationPlane()
        executor = BlockExecutor(state_store, self.conns.consensus,
                                 event_bus=self.event_bus,
                                 speculation=spec_plane)
        wal = WAL(self.wal_path) if self.wal_path else None
        self.cs = ConsensusState(
            fast_consensus_config(), state, executor, block_store,
            wal=wal, event_bus=self.event_bus, speculation=spec_plane,
        )
        self.cs.set_priv_validator(self.pv)
        await self.cs.start()

    async def stop(self):
        if self.cs is not None and self.cs.is_running:
            await self.cs.stop()
        if self.conns is not None and self.conns.is_running:
            await self.conns.stop()


def wire_network(nodes):
    """Relay proposals/parts/votes between all nodes (in lieu of p2p)."""
    for i, src in enumerate(nodes):
        def hook(event, payload, i=i):
            for j, dst in enumerate(nodes):
                if j == i or dst.cs is None or not dst.cs.is_running:
                    continue
                if event == "proposal":
                    dst.cs.add_peer_msg_nowait(m.ProposalMessage(payload), f"n{i}")
                elif event == "block_part":
                    dst.cs.add_peer_msg_nowait(payload, f"n{i}")
                elif event == "vote":
                    dst.cs.add_peer_msg_nowait(m.VoteMessage(payload), f"n{i}")
        src.cs.broadcast_hooks.append(hook)


def test_single_validator_produces_blocks(tmp_path):
    async def go():
        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0], tmp_path)
        await node.start()
        await node.cs.wait_for_height(3, timeout=30)
        assert node.cs.state.last_block_height >= 3
        bs = BlockStore(node.block_db)
        assert bs.height >= 3
        b2 = bs.load_block(2)
        assert b2 is not None and b2.header.height == 2
        # every block carries a full commit from height-1
        assert b2.last_commit.height == 1
        assert node.app.height >= 3
        await node.stop()

    asyncio.run(go())


def test_single_validator_restart_recovers(tmp_path):
    async def go():
        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0], tmp_path)
        await node.start()
        await node.cs.wait_for_height(2, timeout=30)
        h_stop = node.cs.state.last_block_height
        await node.stop()

        # full restart from disk: state store + block store + app + WAL
        node2 = Node(gdoc, pvs[0], tmp_path)
        await node2.start()
        assert node2.cs.state.last_block_height >= h_stop
        await node2.cs.wait_for_height(h_stop + 2, timeout=30)
        bs = BlockStore(node2.block_db)
        assert bs.height >= h_stop + 2
        await node2.stop()

    asyncio.run(go())


def test_four_validator_network(tmp_path):
    async def go():
        gdoc, pvs = make_genesis(4)
        nodes = [Node(gdoc, pv) for pv in pvs]
        for n in nodes:
            await n.start()
        wire_network(nodes)
        await asyncio.gather(*[
            n.cs.wait_for_height(3, timeout=60) for n in nodes
        ])
        hashes = set()
        for n in nodes:
            bs = BlockStore(n.block_db)
            b = bs.load_block(3)
            assert b is not None
            hashes.add(b.hash())
        assert len(hashes) == 1, "all nodes must agree on block 3"
        for n in nodes:
            await n.stop()

    asyncio.run(go())


def test_non_validator_node_follows(tmp_path):
    """A node with no privval (full node) keeps up via gossip."""

    async def go():
        gdoc, pvs = make_genesis(4)
        nodes = [Node(gdoc, pv) for pv in pvs]
        observer = Node(gdoc, None)
        all_nodes = nodes + [observer]
        for n in all_nodes:
            await n.start()
        wire_network(all_nodes)
        await asyncio.gather(*[
            n.cs.wait_for_height(2, timeout=60) for n in all_nodes
        ])
        bs = BlockStore(observer.block_db)
        assert bs.load_block(2) is not None
        for n in all_nodes:
            await n.stop()

    asyncio.run(go())


def test_handshake_replays_into_fresh_app(tmp_path):
    """Blow away the app db only; handshake must replay all blocks
    (the 'app crashed and lost its state' case, replay.go:285)."""

    async def go():
        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0], tmp_path)
        await node.start()
        await node.cs.wait_for_height(3, timeout=30)
        final_apphash = node.app.app_hash
        h = node.app.height
        await node.stop()

        # new empty app db, same state/blocks
        node.app_db = MemDB()
        app2 = PersistentKVStoreApp(node.app_db)
        conns = AppConns(ClientCreator(app=app2))
        await conns.start()
        state_store = Store(node.state_db)
        block_store = BlockStore(node.block_db)
        state = await handshake_and_load_state(
            None, state_store, block_store, gdoc, conns,
        )
        assert app2.height == state.last_block_height
        # replayed app must land on an app hash consistent with state
        assert app2.app_hash == state.app_hash
        assert app2.height >= h - 1
        await conns.stop()

    asyncio.run(go())


def test_handshake_app_ahead_of_state(tmp_path):
    """Crash between app Commit and state save: app_height ==
    store_height == state_height+1. Handshake must bring tendermint
    state forward WITHOUT re-executing the block on the app
    (replay.go:370-415 mock-app path)."""

    async def go():
        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0], tmp_path)
        await node.start()
        await node.cs.wait_for_height(3, timeout=30)
        await node.stop()

        # simulate the crash window: roll tendermint state back one
        # height while keeping block store + app at H
        state_store = Store(node.state_db)
        block_store = BlockStore(node.block_db)
        state = state_store.load()
        H = block_store.height
        assert state.last_block_height == H
        prev = state_store.load()  # rebuild state as-of H-1
        block_h = block_store.load_block(H)
        prev.last_block_height = H - 1
        prev.last_block_id = block_h.header.last_block_id
        prev.last_block_time = block_store.load_block(H - 1).header.time
        prev.app_hash = block_h.header.app_hash  # app hash after H-1
        prev.last_results_hash = block_h.header.last_results_hash
        state_store.save(prev)

        app2 = PersistentKVStoreApp(node.app_db)  # still at height H
        assert app2.height == H
        deliver_count = {"n": 0}
        orig = app2.deliver_tx
        app2.deliver_tx = lambda req: (deliver_count.__setitem__("n", deliver_count["n"] + 1), orig(req))[1]
        conns = AppConns(ClientCreator(app=app2))
        await conns.start()
        state2 = await handshake_and_load_state(
            None, state_store, block_store, gdoc, conns,
        )
        assert state2.last_block_height == H
        assert state2.app_hash == app2.app_hash
        assert deliver_count["n"] == 0  # app was NOT re-driven
        await conns.stop()

    asyncio.run(go())


def test_catchup_parts_complete_despite_stale_proposal(tmp_path):
    """Commit-time catch-up regression (found by the statesync e2e
    under suite load): a node holding a STALE proposal for round-0
    block A receives, at commit time, the parts of the DECIDED block
    B (part set re-initialized by _enter_commit from the +2/3 block
    id). Completion must be judged against the part-set header, not
    the unrelated proposal — the old check rejected the decided block
    and wedged the late joiner behind the net permanently."""
    async def go():
        from tendermint_tpu.consensus import messages as m
        from tendermint_tpu.types.block import BlockID, PartSet
        from tendermint_tpu.types.proposal import Proposal

        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0])
        await node.start()
        try:
            await node.cs.wait_for_height(2, timeout=30)
            cs = node.cs
            rs = cs.rs
            # block B: a real decodable block (reuse block 1 content,
            # it only needs to assemble; completion happens before
            # height checks)
            bs = BlockStore(node.block_db)
            block_b = bs.load_block(1)
            ps_b = block_b.make_part_set(128)
            # stale proposal for a DIFFERENT block id / part set
            rs.proposal = Proposal(
                height=rs.height, round=0, pol_round=-1,
                block_id=BlockID(
                    b"\xaa" * 32,
                    type(ps_b.header())(total=1, hash=b"\xbb" * 32)),
            )
            # _enter_commit's reinit: accept B's part set
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet(ps_b.total, ps_b.hash)
            for i in range(ps_b.total):
                added = cs._add_proposal_block_part(
                    m.BlockPartMessage(rs.height, rs.round,
                                       ps_b.get_part(i)))
                assert added
            assert rs.proposal_block is not None
            assert rs.proposal_block.hash() == block_b.hash()
        finally:
            await node.stop()

    asyncio.run(go())


def test_round_state_event_catalog_publishes():
    """The full reference event catalog (types/events.go:28-38) is
    publishable and routable by tm.event query — incl. the round-4
    additions Relock/Unlock/ValidBlock/TimeoutPropose/TimeoutWait."""
    async def go():
        from tendermint_tpu.types.events import (EventDataRoundState,
                                                 query_for_event)
        bus = EventBus()
        names = ["NewRoundStep", "NewRound", "CompleteProposal",
                 "Polka", "Lock", "Relock", "Unlock", "ValidBlock",
                 "TimeoutPropose", "TimeoutWait", "Vote"]
        subs = {n: bus.subscribe(f"s-{n}", query_for_event(n))
                for n in names if n != "Vote"}
        for n, pub in [
            ("NewRoundStep", bus.publish_new_round_step),
            ("NewRound", bus.publish_new_round),
            ("CompleteProposal", bus.publish_complete_proposal),
            ("Polka", bus.publish_polka),
            ("Lock", bus.publish_lock),
            ("Relock", bus.publish_relock),
            ("Unlock", bus.publish_unlock),
            ("ValidBlock", bus.publish_valid_block),
            ("TimeoutPropose", bus.publish_timeout_propose),
            ("TimeoutWait", bus.publish_timeout_wait),
        ]:
            pub(EventDataRoundState(5, 1, n))
            msg = await asyncio.wait_for(subs[n].next(), timeout=5)
            assert msg.data.height == 5 and msg.data.step == n, n

    asyncio.run(go())


def test_timeout_propose_event_fires_when_proposer_absent(tmp_path):
    """A 2-validator net with one validator offline: rounds where the
    dead node is proposer hit the propose timeout, and the state
    machine publishes TimeoutPropose (reference state.go:854)."""
    async def go():
        from tendermint_tpu.types.events import query_for_event
        gdoc, pvs = make_genesis(2)
        node = Node(gdoc, pvs[0], None)
        await node.start()
        sub = node.event_bus.subscribe("t", query_for_event("TimeoutPropose"))
        try:
            msg = await asyncio.wait_for(sub.next(), timeout=30)
            assert msg.data.height >= 1
        finally:
            await node.stop()

    asyncio.run(go())


def _stray_vote(gdoc, height=999):
    """A well-formed vote the node cannot place (a far height): the
    cheapest message the receive routine can be kept busy with."""
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteType

    val = gdoc.validator_set().validators[0]
    return Vote(VoteType.PREVOTE, height, 0,
                BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32)),
                1_700_000_000_000_000_000, val.address, 0, b"\x33" * 64)


def test_a_stream_of_votes_does_not_hold_a_timeout_back():
    """The receive routine takes one message of EACH source a turn: a
    funnel that never runs empty (10,000 validators' votes behind ten
    peers) must not keep the propose timeout from firing."""
    async def go():
        from tendermint_tpu.consensus.cstypes import RoundStep

        gdoc, _ = make_genesis(4)
        node = Node(gdoc, None)      # follows; nobody proposes
        await node.start()
        cs = node.cs
        msg = m.VoteMessage(_stray_vote(gdoc))
        handed = 0

        async def flood():
            nonlocal handed
            while True:
                await cs.add_peer_msg(msg, "flood")   # back-pressure
                handed += 1

        floods = [asyncio.ensure_future(flood()) for _ in range(4)]
        try:
            loop = asyncio.get_running_loop()
            deadline = loop.time() + 20
            while cs.rs.step < RoundStep.PREVOTE:
                assert loop.time() < deadline, (cs.rs.step, handed)
                await asyncio.sleep(0.05)
            # the funnel was full all the while
            assert cs.peer_funnel.high_depth() > 0 and handed > 1000
        finally:
            for f in floods:
                f.cancel()
            await asyncio.gather(*floods, return_exceptions=True)
            await node.stop()

    asyncio.run(go())


@pytest.mark.parametrize("kind", ["stale_height", "tallied_duplicate"])
def test_net_commits_under_a_stream_of_votes_that_add_nothing(kind):
    """The scheduler holds its cut for a burst that is still arriving,
    and only for that: a peer that keeps the funnel's vote class from
    ever running empty with votes that add nothing to the buffer (a
    far height's, dropped by the sync path; copies of a precommit the
    LastCommit holds, dropped as duplicates) must not keep the four
    validators' own votes, far from a full batch, from being cut and
    tallied (REVIEW, PR 39: the hold had no deadline)."""
    async def go():
        from tendermint_tpu.types.vote import Vote, VoteType

        gdoc, pvs = make_genesis(4)
        nodes = [Node(gdoc, pv) for pv in pvs]
        for n in nodes:
            await n.start()
        wire_network(nodes)
        cs = nodes[0].cs
        handed = 0

        def useless() -> m.VoteMessage:
            if kind == "stale_height":
                return m.VoteMessage(_stray_vote(gdoc))
            # a precommit of the height just committed, as the seen
            # commit has it: a LastCommit vote the node holds already
            h = cs.rs.height - 1
            seen = cs.block_store.load_seen_commit(h)
            idx, sig = next((i, c) for i, c in enumerate(seen.signatures)
                            if not c.is_absent())
            return m.VoteMessage(Vote(
                VoteType.PRECOMMIT, h, seen.round,
                sig.block_id_for(seen.block_id), sig.timestamp,
                sig.validator_address, idx, sig.signature))

        async def flood():
            # never empty, never full: wire_network's hooks (the real
            # votes) raise on a full funnel
            nonlocal handed
            while True:
                if cs.peer_funnel.high_depth() < 64:
                    msg = useless()
                    for _ in range(64):
                        cs.add_peer_msg_nowait(msg, "flood")
                    handed += 64
                await asyncio.sleep(0)

        try:
            await cs.wait_for_height(1, timeout=60)
            flooder = asyncio.ensure_future(flood())
            try:
                h0 = cs.rs.height
                await cs.wait_for_height(h0 + 4, timeout=60)
                assert not flooder.done(), flooder.exception()
                assert cs.peer_funnel.high_depth() > 0 and handed >= 512
            finally:
                flooder.cancel()
                await asyncio.gather(flooder, return_exceptions=True)
        finally:
            for n in nodes:
                await n.stop()

    asyncio.run(go())


def test_a_trickle_cannot_stretch_the_hold_past_its_windows(monkeypatch):
    """Votes that DO add to the buffer, one in every window the
    scheduler holds, with the funnel never empty: the hold ends after
    _HOLD_WINDOWS windows all the same and the batch is cut (garbage
    signatures under a validator's index cost the sender nothing)."""
    async def go():
        from tendermint_tpu.consensus import state as cstate
        from tendermint_tpu.crypto import batch
        from tendermint_tpu.libs import tracing

        gdoc, _ = make_genesis(4)
        node = Node(gdoc, None)      # follows; nobody proposes
        await node.start()
        cs = node.cs
        prev = batch.set_force_host(True)
        tracing.TRACER.clear()
        stray = m.VoteMessage(_stray_vote(gdoc))
        window = cs.config.vote_batch_window_ms / 1e3
        real_sleep = asyncio.sleep

        async def sleep(delay, *args):
            if delay == window:      # the scheduler's: one more lane
                # placeable (our height, validator 0), never tallied:
                # every copy is one more lane of the buffer
                assert cs._enqueue_vote(
                    _stray_vote(gdoc, height=cs.rs.height), "trickle")
                while cs.peer_funnel.high_depth() < 512:
                    cs.add_peer_msg_nowait(stray, "flood")
            return await real_sleep(delay, *args)

        def cuts():
            return [r[6]["lanes"] for r in tracing.TRACER.snapshot()
                    if r[0] == tracing.CONSENSUS_VOTE_QUEUE_WAIT]

        try:
            monkeypatch.setattr(asyncio, "sleep", sleep)
            deadline = asyncio.get_running_loop().time() + 30
            while len(cuts()) < 3:
                assert asyncio.get_running_loop().time() < deadline, cuts()
                if not cs._vote_buf:     # wakes the scheduler
                    assert cs._enqueue_vote(_stray_vote(
                        gdoc, height=cs.rs.height), "trickle")
                await real_sleep(0.01)
            # a lane a window and the one that woke the scheduler
            assert max(cuts()) <= cstate._HOLD_WINDOWS + 2, cuts()
            assert cs.peer_funnel.high_depth() > 0
        finally:
            monkeypatch.setattr(asyncio, "sleep", real_sleep)
            batch.set_force_host(prev)
            await node.stop()

    asyncio.run(go())


def test_wal_records_a_peer_message_as_it_came_off_the_wire(tmp_path):
    """The reactor hands the funnel the bytes beside the decoded
    message, and the WAL records THOSE before the message is handled:
    no second encoding of 20,000 votes a height, and what a replay
    decodes is what the peer sent."""
    async def go():
        gdoc, _ = make_genesis(4)
        node = Node(gdoc, None, tmp_path)
        await node.start()
        vote = _stray_vote(gdoc, height=1)
        canonical = m.encode_consensus_msg(m.VoteMessage(vote))
        # a field this build does not know: decoded over, kept on disk
        raw = canonical + b"\x70\x01"
        assert m.decode_consensus_msg(raw).vote == vote
        await node.cs.add_peer_msg(m.decode_consensus_msg(raw), "wire", raw)
        await node.cs.add_peer_msg(m.VoteMessage(vote), "hook")   # no bytes
        for _ in range(200):
            if node.cs.peer_funnel.qsize() == 0:
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)
        await node.stop()
        from tendermint_tpu.consensus.wal import MsgInfo

        seen = {r.msg.peer_id: r.msg.msg_bytes
                for r in WAL.decode_all(node.wal_path)
                if isinstance(r.msg, MsgInfo)}
        assert seen["wire"] == raw and seen["hook"] == canonical

    asyncio.run(go())
