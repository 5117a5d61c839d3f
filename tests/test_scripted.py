"""An ordinary node following LIVE consensus behind scripted peers
(tendermint_tpu/sim/scripted.py), compared with the plain round-0
follower (tests/consensus_model.py): block IDs, app hashes, the vote
sets' members, the seen commits, the planted votes' fate and the
shed-and-redelivered case. The tier-1 twin of tests/test_scale_10k.py
and of the benchmark cell `consensus10k.live`: the same node, the same
peers, 64 and 256 validators instead of 10,000."""

import asyncio
import json
import os

import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey)

import consensus_model as model
from benchmark.reference import ed25519_zip215 as ref
from helpers import make_genesis
from tendermint_tpu.abci.kvstore import KVStoreApp
from tendermint_tpu.config import Config
from tendermint_tpu.node import Node
from tendermint_tpu.sim.scripted import (
    VOTE_TYPES, HeldVotes, ScriptedChain, ScriptedNet)
from tendermint_tpu.types.vote import VoteType


@pytest.fixture(autouse=True)
def host_verify():
    """Every launch on the host: at these sizes a CPU-backend kernel
    launch is tens of seconds (and a 12,288-lane arena minutes). The
    device path of the same node is the benchmark's rehearsal and the
    chip's."""
    from tendermint_tpu.crypto import batch

    prev = batch.set_force_host(True)
    yield
    batch.set_force_host(prev)


def zip215(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The copied ZIP-215 verifier's verdict at OpenSSL's speed: what
    RFC 8032 accepts ZIP-215 accepts, so only a refusal is asked
    again (6 ms a signature)."""
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return ref.verify(pub, msg, sig)


def make_chain(n_vals, heights, seed, **kw):
    gdoc, pvs = make_genesis(n_vals, power=1, chain_id="scripted-test")
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    ordered = [by_addr[v.address]
               for v in gdoc.validator_set().validators]

    def sign(items):
        return [ordered[i].priv_key.sign(msg) for i, msg in items]

    return ScriptedChain(gdoc, KVStoreApp(), sign, heights=heights,
                         seed=seed, **kw)


def node_config(tmp_path, gdoc, **consensus):
    home = str(tmp_path / "node")
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    cfg = Config()
    cfg.base.home = home
    cfg.base.fast_sync = False      # it follows; it has nothing to sync
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = ""
    for k, v in consensus.items():   # shortened IN THE TEST ONLY
        setattr(cfg.consensus, k, v)
    gdoc.save(os.path.join(home, "config", "genesis.json"))
    return cfg


async def settled(cs, net):
    """The peers hand over nothing more; what the reactor had in its
    hands is through the funnel and what the scheduler held is
    tallied: the node acknowledges nothing after this returns."""
    net.pause()
    quiet = 0
    while quiet < 3:
        await asyncio.sleep(0.05)
        quiet = quiet + 1 if cs._vote_idle.is_set() and \
            not cs.peer_funnel.qsize() else 0


async def follow(tmp_path, chain, peers=4, upto=None, timeout=120,
                 query_maj23_s=2.0, settle=False, **consensus):
    """Boot the ordinary node, attach the scripted net, wait until the
    node has PROPOSED height `upto` + 1 (so `upto`'s late precommits
    are in), stop (`settle`: once the node has finished what it was
    handed); return what there is to compare."""
    upto = upto or len(chain.heights) - 1
    cfg = node_config(tmp_path, chain.gdoc, timeout_commit_ms=150,
                      **consensus)
    node = Node.default_new_node(cfg)
    await node.start()
    cs = node.consensus_state
    watch = HeldVotes(cs, chain)
    net = ScriptedNet(chain, peers, query_maj23_s=query_maj23_s)
    try:
        assert cs.priv_validator_address not in {
            v.address for v in cs.rs.validators.validators}
        await net.attach(node.switch, node.consensus_reactor)
        deadline = asyncio.get_running_loop().time() + timeout
        while upto not in watch.precommits:
            assert asyncio.get_running_loop().time() < deadline, \
                (cs.rs.height, cs.rs.step, net.handed_over())
            await asyncio.sleep(0.02)
        if settle:
            await settled(cs, net)
        out = {
            "watch": watch, "net": net, "acked": net.acknowledged(),
            "block_ids": {h: node.block_store.load_block_meta(h).block_id
                          for h in range(1, upto + 1)},
            "app_hashes": {h: node.block_store.load_block_meta(
                h + 1).header.app_hash for h in range(1, upto)},
            "seen": {h: node.block_store.load_seen_commit(h)
                     for h in range(1, upto + 1)},
            "trust": {p.id: node.switch.reporter.trust.get_metric(p.id)
                      for p in net.peers},
            "shed": cs._vote_shed,
            "patched": node.speculation.patched_lanes,
        }
    finally:
        await net.stop(node.switch)
        await node.stop()
    return out


def follow_model(chain, upto):
    """The plain follower over everything the peers hold, planted
    copies first (they arrive first)."""
    vals = [(v.pub_key.bytes(), v.voting_power)
            for v in chain.validators.validators]
    follower = model.Follower(chain.chain_id, vals, verify=zip215)
    outcomes = {}
    for hs in chain.heights[:upto]:
        votes = [(int(p.type), p.lane, int(hs.times[p.type][
            list(hs.lanes[p.type]).index(p.lane)]), p.signature)
            for p in hs.planted]
        for vtype in VOTE_TYPES:
            votes += [(int(vtype), int(i), int(t), s) for i, t, s in zip(
                hs.lanes[vtype], hs.times[vtype], hs.sigs[vtype])]
        psh = hs.block_id.part_set_header
        outcomes[hs.height] = follower.follow(
            hs.height, hs.block_id.hash, psh.total, psh.hash, hs.txs, votes)
    return follower, outcomes


def compare(chain, got, upto, whole_sets=True):
    follower, outcomes = follow_model(chain, upto)
    for h in range(1, upto + 1):
        hs, want = chain.at(h), outcomes[h]
        assert want.polka and want.block_hash == hs.block_id.hash
        # agreement and the app-hash oracle
        assert got["block_ids"][h] == hs.block_id
        if h < upto:
            assert got["app_hashes"][h] == want.app_hash == hs.app_hash
        # the planted copies: refused by the model, in no set
        planted = {(int(p.type), p.lane, p.signature) for p in hs.planted}
        assert planted and planted == set(want.refused)
        # members: nothing the model would not hold, > 2/3, and every
        # acknowledged vote; without shedding, all of them
        assert got["watch"].rounds[h] == 0
        held = {VoteType.PREVOTE: HeldVotes.members(
                    got["watch"].prevotes[h]),
                VoteType.PRECOMMIT: HeldVotes.members(
                    got["watch"].precommits[h])}
        for vtype in VOTE_TYPES:
            full = want.members[int(vtype)]
            assert held[vtype] <= full
            # a commit needs > 2/3 of the PRECOMMITS; where prevotes
            # were shed and come back late it can come before the polka
            if vtype == VoteType.PRECOMMIT or whole_sets:
                assert follower.holds_two_thirds(held[vtype])
            assert got["acked"].get((h, int(vtype)), set()) <= held[vtype]
            if whole_sets:
                assert held[vtype] == full == got["acked"][h, int(vtype)]
        # the seen commit: > 2/3, and only signatures that were sent
        seen = got["seen"][h]
        sent = dict(zip(hs.lanes[VoteType.PRECOMMIT].tolist(),
                        hs.sigs[VoteType.PRECOMMIT]))
        signed = [i for i, cs_ in enumerate(seen.signatures)
                  if not cs_.is_absent()]
        assert seen.block_id == hs.block_id
        assert follower.holds_two_thirds(signed)
        assert all(seen.signatures[i].signature == sent[i] for i in signed)
    return outcomes


@pytest.mark.parametrize("n_vals,heights", [(64, 5), (256, 5)])
def test_node_follows_scripted_chain_like_the_model(tmp_path, n_vals,
                                                    heights):
    chain = make_chain(n_vals, heights, seed=n_vals)
    # no VoteSetMaj23 inside the run: each vote handed over ONCE has to
    # do (a query that finds votes still in the funnel has them sent
    # again, which a loaded machine would turn into this test's luck)
    got = asyncio.run(follow(tmp_path, chain, query_maj23_s=600.0))
    compare(chain, got, heights - 1)
    assert got["shed"] == 0 and got["net"].redelivered() == 0


def test_planted_votes_debit_their_sender(tmp_path):
    """A spoiled copy arrives from another peer than the good one: it
    is never tallied, the good copy is, and the spoiled copy's sender
    is the one the trust metric debits."""
    chain = make_chain(64, 4, seed=7, planted_per_1000=10)
    got = asyncio.run(follow(tmp_path, chain))
    compare(chain, got, 3)
    net = got["net"]
    senders = {peer.id for peer in net.peers
               for (h, _), share in peer.shares.items()
               if h <= 3 and any(pos < 0 for pos in share.order)}
    assert senders and len(senders) < len(net.peers)
    for peer in net.peers:
        # the interval's own tally (p2p/trust.py): 60 s, never ticked
        # inside this test
        bad = got["trust"][peer.id].bad
        assert (bad > 0) == (peer.id in senders), (peer.id, bad)
        # one debit a planted copy that peer handed over
        assert bad >= sum(1 for at in net.planted_at
                          if at[4] == peer.id and at[1] <= 3)
    # and of every planted vote the node's sets hold the good copy
    kept = got["watch"].signatures
    assert net.planted_at and got["watch"].spoiled() == []
    assert {k for k in kept if k[0] <= 3} == {
        (hs.height, int(p.type), p.lane)
        for hs in chain.heights[:3] for p in hs.planted}


def test_a_node_that_tallies_unverified_votes_is_seen(tmp_path, monkeypatch):
    """The control of the planted votes' check: with the scheduler's
    verdicts forced true the node adds the spoiled copies (they come
    first), its vote sets hold their signatures, and a spoiled
    precommit stands in a seen commit."""
    import numpy as np

    from tendermint_tpu.consensus.state import ConsensusState

    monkeypatch.setattr(
        ConsensusState, "_batch_verdicts",
        lambda self, batch, chain_id: np.ones(len(batch), bool))
    chain = make_chain(64, 4, seed=7, planted_per_1000=30)
    got = asyncio.run(follow(tmp_path, chain))
    planted = {(hs.height, int(p.type), p.lane)
               for hs in chain.heights[:3] for p in hs.planted}
    assert planted and planted <= set(got["watch"].spoiled())
    assert {int(p.type) for hs in chain.heights[:3]
            for p in hs.planted} == {1, 2}
    for hs in chain.heights[:3]:
        for p in hs.planted:
            if p.type == VoteType.PRECOMMIT:
                assert got["seen"][hs.height].signatures[
                    p.lane].signature == p.signature


def test_shed_votes_come_back_through_vote_set_bits(tmp_path):
    """`vote_buf_max` forced small: the scheduler's buffer sheds, the
    peers' VoteSetMaj23 draws VoteSetBits answers that show what is
    lacking, those votes are handed over again, and the chain still
    advances on verified votes only."""
    from tendermint_tpu.libs import tracing

    chain = make_chain(256, 4, seed=11)
    tracing.TRACER.clear()
    got = asyncio.run(follow(
        tmp_path, chain, upto=3, query_maj23_s=0.2, timeout=180,
        vote_buf_max=48, vote_batch_max=32, timeout_prevote_ms=30_000,
        timeout_precommit_ms=30_000, timeout_propose_ms=30_000))
    compare(chain, got, 3, whole_sets=False)
    assert got["net"].redelivered() > 0
    # vote_batch_max lanes a launch and no more, however deep the buffer
    lanes = [rec[6]["lanes"] for rec in tracing.TRACER.snapshot()
             if rec[0] == tracing.CONSENSUS_VOTE_BATCH]
    assert lanes and max(lanes) <= 32 and lanes.count(32) > 3


def test_prevote_burst_polka_commit_and_next_last_commit(tmp_path):
    """tests/test_scale_10k.py's tier-1 twin at 256 validators, on the
    path the cell measures: a prevote burst through the reactor and
    the vote scheduler reaches the polka, the precommits commit the
    block, and the next height's LastCommit (validated by
    apply_block) is the scripted one."""
    chain = make_chain(256, 3, seed=3)
    got = asyncio.run(follow(tmp_path, chain, upto=2))
    compare(chain, got, 2)
    # block 2 carries height 1's scripted precommits as its LastCommit
    # and the node applied it: every one of them was checked
    hs = chain.at(2)
    assert got["block_ids"][2] == hs.block_id
    assert sum(1 for s in hs.block.last_commit.signatures
               if not s.is_absent()) == len(
                   chain.at(1).lanes[VoteType.PRECOMMIT])
    # the speculation plane was shown every precommit the node took,
    # those that came after the +2/3 (into the LastCommit) too: the
    # next block's LastCommit check has a lane for each of them
    assert got["patched"] == sum(
        len(v) for (_, vtype), v in got["acked"].items()
        if vtype == int(VoteType.PRECOMMIT))


def test_prevotes_tallied_in_the_commit_step_are_held(tmp_path, monkeypatch):
    """The block's part reaches the node only after the +2/3 of
    precommits (parts are the funnel's low class: behind a 10,000-vote
    burst that is the usual order), and one peer of four brings its
    prevotes only then: the node sits in its commit step without the
    block and tallies and acknowledges those prevotes AFTER the step
    that first showed the set. HeldVotes reads the set as the node
    leaves it, so every acknowledged vote is in it (a copy taken at the
    commit step read 511 acknowledged prevotes as missing in one chip
    run of PR 40's first tree)."""
    from tendermint_tpu.consensus import messages as m
    from tendermint_tpu.consensus.cstypes import RoundStep
    from tendermint_tpu.consensus.reactor import (
        DATA_CHANNEL, ConsensusReactor)
    from tendermint_tpu.sim import scripted

    chain = make_chain(64, 4, seed=13)
    real_receive = ConsensusReactor.receive
    real_hand_over = scripted.ScriptedPeer._hand_over
    part_tag = m._TAG[m.BlockPartMessage]
    late, parts = [], []

    async def in_commit(cs, height):
        while cs.rs.height == height and cs.rs.step != RoundStep.COMMIT:
            await asyncio.sleep(0.001)
        return cs.rs.height == height

    async def part_after_the_commit_step(self, peer, msgb):
        if await in_commit(self.cs, m.decode_consensus_msg(msgb).height):
            await asyncio.sleep(0.2)
        await real_receive(self, DATA_CHANNEL, peer, msgb)

    async def receive(self, chan_id, peer, msgb):
        if chan_id == DATA_CHANNEL and msgb[0] == part_tag:
            parts.append(asyncio.ensure_future(   # the dealer streams on
                part_after_the_commit_step(self, peer, msgb)))
        else:
            await real_receive(self, chan_id, peer, msgb)

    async def hand_over(self, reactor, hs, vtype, share, positions):
        if self.index == 1 and vtype == VoteType.PREVOTE \
                and positions is share.order:
            late.append((hs.height, await in_commit(reactor.cs, hs.height)))
        await real_hand_over(self, reactor, hs, vtype, share, positions)

    monkeypatch.setattr(ConsensusReactor, "receive", receive)
    monkeypatch.setattr(scripted.ScriptedPeer, "_hand_over", hand_over)
    got = asyncio.run(follow(tmp_path, chain, upto=3,
                             timeout_propose_ms=30_000))
    assert late[:3] == [(1, True), (2, True), (3, True)]
    for h in range(1, 4):
        held = HeldVotes.members(got["watch"].prevotes[h])
        acked = got["acked"][h, int(VoteType.PREVOTE)]
        assert acked <= held
        share = {int(chain.at(h).lanes[VoteType.PREVOTE][p])
                 for p in got["net"].peers[1].shares[
                     h, VoteType.PREVOTE].order if p >= 0}
        assert share and share <= acked   # tallied in the commit step


def test_next_heights_proposal_waits_for_the_votes_ahead_of_it(tmp_path):
    """The funnel hands messages over in the order they came, but a
    vote goes on through the scheduler while a proposal is handled at
    once: the proposal of height 2, queued right behind the precommits
    that end height 1, used to find the node still at height 1 and be
    dropped (to come again only by gossip). The intake now waits for
    the votes ahead of such a message."""
    from tendermint_tpu.consensus import messages as m

    chain = make_chain(16, 3, seed=21, planted_per_1000=0)

    async def go():
        cfg = node_config(tmp_path, chain.gdoc, timeout_commit_ms=50,
                          timeout_propose_ms=60_000)
        node = Node.default_new_node(cfg)
        await node.start()
        cs = node.consensus_state
        try:
            h1, h2 = chain.at(1), chain.at(2)
            stream = [h1.proposal, *h1.part_msgs,
                      *h1.msgs[VoteType.PREVOTE],
                      *h1.msgs[VoteType.PRECOMMIT],
                      h2.proposal, *h2.part_msgs]
            for raw in stream:
                await cs.add_peer_msg(m.decode_consensus_msg(raw), "p", raw)
            loop = asyncio.get_running_loop()
            deadline = loop.time() + 30
            while not (cs.rs.height == 2 and cs.rs.proposal_block
                       is not None):
                assert loop.time() < deadline, (
                    cs.rs.height, cs.rs.step, cs.rs.proposal)
                await asyncio.sleep(0.01)
            assert cs.rs.round == 0
            assert cs.rs.proposal_block.hash() == h2.block_id.hash
        finally:
            await node.stop()

    asyncio.run(go())


def test_vote_path_spans_are_one_a_batch_not_one_a_vote(tmp_path):
    """The four span kinds around consensus.vote_batch: a folded
    consensus.receive and consensus.has_vote (their `n` add up to the
    messages and the acknowledgements, an entry a run), a queue wait
    and a tally a micro-batch — a few dozen ring entries for hundreds
    of votes."""
    from tendermint_tpu.libs import tracing

    chain = make_chain(64, 4, seed=5)
    tracing.TRACER.clear()
    # settled: a batch tallied between the reading of `acked` and the
    # node's stop would be acknowledgements the spans count and it
    # does not
    got = asyncio.run(follow(tmp_path, chain, settle=True))
    by_kind: dict[str, list] = {}
    for rec in tracing.TRACER.snapshot():
        by_kind.setdefault(rec[0], []).append(rec)
    acked = sum(len(v) for v in got["acked"].values())
    handed = got["net"].handed_over()
    verifies = by_kind[tracing.CONSENSUS_VOTE_BATCH]
    tallies = by_kind[tracing.CONSENSUS_VOTE_TALLY]
    # one of each a micro-batch; the node is stopped while height 4
    # streams in, so the last batch may have been cut and not tallied
    assert len(by_kind[tracing.CONSENSUS_VOTE_QUEUE_WAIT]) >= len(
        verifies) >= len(tallies) >= len(verifies) - 1
    batches = len(tallies)
    # what ended each hold: the batch was full, the burst was over, or
    # the hold's cap
    assert {r[6]["cut"] for r in by_kind[
        tracing.CONSENSUS_VOTE_QUEUE_WAIT]} <= {"full", "idle", "cap"}
    assert sum(r[6]["added"] for r in tallies) == acked
    # every spoiled copy once: all of the three whole heights', and of
    # height 4's the ones the peers had handed over when they paused
    late = sum(1 for at in got["net"].planted_at if at[1] == 4)
    assert sum(r[6]["rejected"] for r in tallies) == late + sum(
        len(chain.at(h).planted) for h in range(1, 4))
    assert sum(r[6]["votes"] for r in tallies) == sum(
        r[6]["lanes"] for r in verifies[:batches])
    has_vote = by_kind[tracing.CONSENSUS_HAS_VOTE]
    units = lambda r: (r[6] or {}).get("n", 1)   # a lone unit has no n
    assert sum(units(r) for r in has_vote) == acked
    receive = by_kind[tracing.CONSENSUS_RECEIVE]
    busy = lambda r: (r[6] or {}).get("busy_ns", r[5])
    # every vote, proposal and part of the three whole heights, once
    # each; what height 4 had brought when the node was stopped beside
    whole = sum(hs.signatures() + len(hs.planted) + 1 + len(hs.part_msgs)
                for hs in chain.heights[:3])
    assert whole <= sum(units(r) for r in receive) <= handed + 8
    # beside them the two sums of the intake's decode: every vote of
    # the three heights came in the canonical layout, and the reactor's
    # share (decode + marks) is part of the units' own time
    votes = sum(hs.signatures() + len(hs.planted) for hs in chain.heights[:3])
    assert votes <= sum(r[6]["shaped"] for r in receive) <= handed
    assert 0 < sum(r[6]["decode_ms"] for r in receive) * 1e6 <= sum(
        busy(r) for r in receive)
    # the benchmark's two metrics read these sums by their names
    for metric in ("vote_decode_ms_per_height.live",
                   "vote_decode_shaped_per_height.live"):
        with open(os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "..",
                "benchmark", "layer_metrics", metric + ".json")) as f:
            params = json.load(f)["params"]
        assert params["kind"] == tracing.CONSENSUS_RECEIVE
        assert params["per"] == tracing.CONSENSUS_HEIGHT
        assert all(params["attr"] in r[6] for r in receive)
    # (a receive unit counts the reactor's decode in, which a burst
    # does ahead of the routine: its busy time can pass the entry's)
    odd = [(r[0], r[6], r[5]) for r in receive + has_vote
           if not 0 < busy(r) <= (r[5] if r in has_vote else busy(r))]
    assert not odd, odd[:5]
    # an entry a run (a pause, a new height or 32 other spans end one)
    for folded in (receive, has_vote):
        assert 4 * len(folded) <= sum(units(r) for r in folded)
    assert batches < acked / 4


def test_no_clock_is_read_for_the_vote_path_when_tracing_is_off(
        tmp_path, monkeypatch):
    """The per-message sites (consensus.receive in the reactor and the
    receive routine, consensus.has_vote) read no clock while the tracer
    is off: 20,000 votes a height are 100,000 reads otherwise."""
    import time as real_time
    import types

    from tendermint_tpu.consensus import reactor, state
    from tendermint_tpu.libs import tracing

    reads = []

    def counted():
        reads.append(1)
        return real_time.perf_counter_ns()

    chain = make_chain(16, 3, seed=9)
    clock = types.SimpleNamespace(**{
        k: getattr(real_time, k) for k in dir(real_time)
        if not k.startswith("_")})
    clock.perf_counter_ns = counted
    monkeypatch.setattr(reactor, "time", clock)
    monkeypatch.setattr(state, "_time", clock)
    monkeypatch.setattr(tracing.TRACER, "enabled", False)
    got = asyncio.run(follow(tmp_path, chain, upto=2))
    acked = sum(len(v) for v in got["acked"].values())
    assert acked >= 2 * 2 * 14
    # a read a micro-batch (when its first vote was buffered), none a
    # message: far fewer than the votes
    assert len(reads) < acked / 4, (len(reads), acked)


def test_handed_over_votes_are_shaped_and_their_wal_replays_alike(
        tmp_path, monkeypatch):
    """At the rehearsal's 216 validators: every vote the scripted peers
    hand over is decoded by its shape, the same bytes with an unknown
    field appended by the general decoder, and a restart's WAL catch-up
    of the height in flight (ConsensusState._catchup_replay, which
    decodes the records through the same entry) ends with the votes,
    the tallied sets and the app hash that the field-by-field decoder
    alone replays them to."""
    import shutil

    from tendermint_tpu.consensus import messages as m
    from tendermint_tpu.consensus.reactor import VOTE_CHANNEL

    n_vals, inflight = 216, 3
    chain = make_chain(n_vals, inflight, seed=41)
    unknown = b"\x80\x01\x01"     # field 16, a varint

    def held(cs):
        sets = {}
        for vtype, vs in ((VoteType.PREVOTE, cs.rs.votes.prevotes(0)),
                          (VoteType.PRECOMMIT, cs.rs.votes.precommits(0))):
            sets[int(vtype)] = [
                (v.validator_index, v.timestamp, v.signature, v.block_id)
                for v in vs.votes if v is not None] if vs else []
        return {"height": cs.rs.height, "sets": sets,
                "app_hash": cs.state.app_hash,
                "proposal": cs.rs.proposal_block.hash()
                if cs.rs.proposal_block else None}

    async def live():
        cfg = node_config(tmp_path / "live", chain.gdoc,
                          timeout_commit_ms=150)
        node = Node.default_new_node(cfg)
        await node.start()
        cs = node.consensus_state
        net = ScriptedNet(chain, 4, query_maj23_s=600.0)
        shaped0, general0 = m.vote_decode_counts()
        try:
            await net.attach(node.switch, node.consensus_reactor)
            loop = asyncio.get_running_loop()
            deadline = loop.time() + 120
            # two whole heights, then a third of the next one's
            # prevotes: the height stays in flight
            key = (inflight, VoteType.PREVOTE)
            while sum(len(p.shares[key].sent)
                      for p in net.peers) < n_vals // 3:
                assert loop.time() < deadline, (cs.rs.height, cs.rs.step)
                await asyncio.sleep(0)
            await settled(cs, net)
            shaped, general = m.vote_decode_counts()
            assert shaped - shaped0 == net.handed_over() > 2 * 2 * 144
            assert general == general0
            raw = chain.at(inflight).msgs[VoteType.PREVOTE][0] + unknown
            await node.consensus_reactor.receive(
                VOTE_CHANNEL, net.peers[0], raw)
            assert m.vote_decode_counts() == (shaped, general0 + 1)
            await settled(cs, net)
            before = held(cs)
        finally:
            await net.stop(node.switch)
            await node.stop()
        return cfg.base.home, before

    async def restart(name):
        shutil.copytree(home, str(tmp_path / name / "node"))
        cfg = node_config(tmp_path / name, chain.gdoc,
                          timeout_commit_ms=150)
        counts0 = m.vote_decode_counts()
        node = Node.default_new_node(cfg)
        await node.start()
        try:
            counts = m.vote_decode_counts()
            return held(node.consensus_state), (
                counts[0] - counts0[0], counts[1] - counts0[1])
        finally:
            await node.stop()

    home, before = asyncio.run(live())
    assert before["height"] == inflight and before["proposal"] == \
        chain.at(inflight).block_id.hash
    assert n_vals // 3 <= len(before["sets"][int(VoteType.PREVOTE)]) \
        < 2 * n_vals // 3
    by_shape, (shaped, general) = asyncio.run(restart("by_shape"))
    monkeypatch.setattr(m, "_decode_shaped_vote", lambda data: None)
    by_field, (none, every) = asyncio.run(restart("by_field"))
    assert by_shape == by_field == before
    assert by_shape["app_hash"] == chain.at(inflight - 1).app_hash
    # the WAL holds the bytes as they came: the vote with the unknown
    # field is the general decoder's in the replay too
    assert general == 1 and none == 0
    assert shaped + general == every >= n_vals // 3


def test_model_copies_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "consensus_model.py")) as a, \
            open(os.path.join(here, "..", "benchmark", "reference",
                              "consensus_model.py")) as b:
        assert a.read() == b.read()
