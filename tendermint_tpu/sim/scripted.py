"""Scripted signers and scripted peers (ROADMAP R0a).

A node follows LIVE consensus only if somebody else's validators sign.
At 10,000 validators nobody runs 10,000 nodes: `ScriptedChain` makes,
ahead of time, everything the validators of a chain would have said —
each height's block, its part set, the proposer's Proposal and every
non-absent validator's prevote and precommit, all ENCODED as the
consensus reactor's wire messages — and `ScriptedPeer`s hand those
bytes to an ordinary node through the reactor's own peer interface
(`add_peer`, `receive(chan_id, peer, msgb)`), reading the node's
NewRoundStep, HasVote and VoteSetBits from what it sends them. The
program has no branch that knows a peer is scripted.

Every height commits in round 0. What is left of R0a: a validator's own
votes among scripted ones, rounds above 0, network delay.

The scripted peer's rules (reference consensus/reactor.go
gossipDataRoutine, gossipVotesRoutine, queryMaj23Routine):

  * at the node's NewRoundStep for a height, the height's dealer sends
    the Proposal and then the parts in order (a part that arrives
    before its proposal is dropped by the node);
  * every peer then hands over ITS share of the height's votes in one
    stream, prevotes before precommits (a validator precommits once it
    has seen the polka), as fast as the reactor's back-pressure takes
    them; a vote is marked sent when handed over;
  * precommits the node has not taken when it commits keep coming: the
    node is in NewHeight until `timeout_commit` runs out and takes them
    into its LastCommit, as the reference's does;
  * `ScriptedNet.pause()` stops every hand-over where it stands (the
    peers stay connected) until `resume()`;
  * every `query_maj23_s` the peer claims +2/3 for the height's block
    (VoteSetMaj23, both types); the node's VoteSetBits answer REPLACES
    the peer's marks for its own share, and what the answer shows
    lacking is sent again. Nothing else is ever sent twice.
"""

from __future__ import annotations

import asyncio
import hashlib
from dataclasses import dataclass, field

import numpy as np

from ..abci import types as abci_t
from ..consensus import messages as m
from ..consensus.cstypes import RoundStep
from ..consensus.reactor import (
    DATA_CHANNEL, STATE_CHANNEL, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL)
from ..p2p.node_info import NodeInfo
from ..state import make_genesis_state, median_time
from ..state.execution import update_state
from ..types import canonical
from ..types.block import BlockID, BlockIDFlag, Commit, CommitSig
from ..types.proposal import Proposal
from ..types.vote import Vote, VoteType

VOTE_TYPES = (VoteType.PREVOTE, VoteType.PRECOMMIT)
# wire tags of the three messages a scripted peer reads
_TAG_NEW_ROUND_STEP = m._TAG[m.NewRoundStepMessage]
_TAG_HAS_VOTE = m._TAG[m.HasVoteMessage]
_TAG_VOTE_SET_BITS = m._TAG[m.VoteSetBitsMessage]


def spoil(sig: bytes) -> bytes:
    """A signature that must be refused: one bit of S flipped."""
    b = bytearray(sig)
    b[40] ^= 0x01
    return bytes(b)


@dataclass
class Planted:
    """A vote that arrives first with a spoiled signature, from another
    peer than the one that later brings the good copy."""

    type: VoteType
    lane: int         # validator index
    msg: bytes        # the encoded VoteMessage with the spoiled signature
    signature: bytes  # the spoiled signature


@dataclass
class HeightScript:
    """What the validators of one height said."""

    height: int
    block: object
    parts: object
    block_id: BlockID
    txs: list[bytes]
    app_hash: bytes            # after the block's txs
    proposer: int              # validator index
    proposal: bytes            # encoded ProposalMessage
    part_msgs: list[bytes]     # encoded BlockPartMessages, in order
    # per vote type, the non-absent validators' votes in index order
    lanes: dict = field(default_factory=dict)     # type -> int32 array
    times: dict = field(default_factory=dict)     # type -> int64 array
    sigs: dict = field(default_factory=dict)      # type -> list[bytes]
    msgs: dict = field(default_factory=dict)      # type -> list[bytes]
    planted: list[Planted] = field(default_factory=list)

    def signatures(self) -> int:
        return sum(len(v) for v in self.lanes.values())


class ScriptedChain:
    """`heights` blocks of a kvstore chain whose every height commits
    in round 0, with all that its validators sign.

    `sign(items)` signs: items is a list of (validator index, sign
    bytes) and the answer the list of their 64-byte signatures (the
    tests hand in the program's MockPV, the benchmark its OpenSSL
    pool). `app` is a fresh kvstore application: the chain's app hashes
    are what IT returns. Absent validators (neither vote of a height)
    are `absent_share` of the set, drawn per height by the seed between
    its two bounds; `planted_per_1000` of a height's votes (at least
    one, if not 0) also exist as spoiled copies."""

    def __init__(self, gdoc, app, sign, *, heights: int, seed: int,
                 txs_per_block: int = 8, tx_bytes: int = 64,
                 absent_share: tuple[float, float] = (0.0, 0.03),
                 planted_per_1000: float = 1.0):
        self.gdoc = gdoc
        self.chain_id = gdoc.chain_id
        self.seed = seed
        self.tx_bytes = tx_bytes
        self.heights: list[HeightScript] = []
        self._who: list[bytes] | None = None   # a validator's fields 6, 7
        rng = np.random.default_rng([seed, 0x5C21])
        state = make_genesis_state(gdoc)
        # what the node's handshake does with a fresh app
        # (consensus/replay.py): its version, then InitChain
        state.app_version = app.info(abci_t.RequestInfo()).app_version \
            or state.app_version
        res = app.init_chain(abci_t.RequestInitChain(
            time=gdoc.genesis_time, chain_id=gdoc.chain_id,
            validators=[abci_t.ValidatorUpdate(
                v.pub_key.type_name, v.pub_key.bytes(), v.voting_power)
                for v in state.validators.validators],
            initial_height=gdoc.initial_height))
        if res.app_hash:
            state.app_hash = res.app_hash
        self.validators = state.validators.copy()
        vals = state.validators.validators
        n = len(vals)
        self.n = n
        addrs = [v.address for v in vals]
        last_commit = None
        for h in range(1, heights + 1):
            txs = [self._tx(h, k) for k in range(txs_per_block)]
            when = state.last_block_time if h == 1 else \
                median_time(last_commit, state.validators)
            proposer = state.validators.get_proposer()
            block = state.make_block(h, txs, last_commit, [],
                                     proposer.address, when)
            parts = block.make_part_set()
            bid = BlockID(block.hash(), parts.header())
            app.begin_block(abci_t.RequestBeginBlock())
            delivered = [app.deliver_tx(abci_t.RequestDeliverTx(tx))
                         for tx in txs]
            end = app.end_block(abci_t.RequestEndBlock(h))
            state = update_state(state, bid, block, {
                "deliver_txs": delivered, "end_block": end}, [])
            state.app_hash = app.commit(abci_t.RequestCommit()).data
            if len(state.validators) != n:
                raise ValueError("a scripted chain's set stands still")

            pidx = addrs.index(proposer.address)
            proposal = Proposal(height=h, round=0, pol_round=-1,
                                block_id=bid, timestamp=when + 1_000_000)
            proposal.signature = sign(
                [(pidx, proposal.sign_bytes(self.chain_id))])[0]
            hs = HeightScript(
                height=h, block=block, parts=parts, block_id=bid,
                txs=txs, app_hash=state.app_hash, proposer=pidx,
                proposal=m.encode_consensus_msg(
                    m.ProposalMessage(proposal)),
                part_msgs=[m.encode_consensus_msg(m.BlockPartMessage(
                    h, 0, parts.get_part(i)))
                    for i in range(parts.total)])
            lo, hi = absent_share
            n_absent = int(n * (lo + (hi - lo) * rng.random()))
            present = np.ones(n, bool)
            present[rng.choice(n, n_absent, replace=False)] = False
            lanes = np.flatnonzero(present).astype(np.int32)
            for vtype, after_ns in ((VoteType.PREVOTE, 500_000_000),
                                    (VoteType.PRECOMMIT, 1_000_000_000)):
                times = when + after_ns + lanes.astype(np.int64) * 1_000
                self._sign_votes(hs, vtype, lanes, times, addrs, sign)
            self._plant(hs, planted_per_1000, addrs, rng)
            self.heights.append(hs)
            sig_of = dict(zip(lanes.tolist(),
                              zip(hs.times[VoteType.PRECOMMIT].tolist(),
                                  hs.sigs[VoteType.PRECOMMIT])))
            last_commit = Commit(h, 0, bid, [
                CommitSig(BlockIDFlag.COMMIT, addrs[i], *sig_of[i])
                if i in sig_of else CommitSig.absent()
                for i in range(n)])

    def _tx(self, h: int, k: int) -> bytes:
        tag = hashlib.sha256(
            f"scripted/{self.seed}/{h}/{k}".encode()).hexdigest()
        return (f"k{h:x}.{k:x}=".encode() + tag.encode() * 2)[
            :self.tx_bytes]

    def _sign_votes(self, hs, vtype, lanes, times, addrs, sign) -> None:
        """Sign bytes and wire bytes of a step's votes from what they
        share: one timestamp field a vote serves both (canonical field
        5 and the Vote's are one encoding), the rest is per step or per
        validator. The first vote is held against the program's own
        encoders."""
        from ..encoding.proto import Writer, encode_varint
        from ..types.block import block_id_writer

        pre, suf = canonical.vote_sign_parts(
            self.chain_id, int(vtype), hs.height, 0, hs.block_id)
        head = Writer().varint(1, int(vtype)).varint(2, hs.height) \
            .message(4, block_id_writer(hs.block_id)).finish()
        if self._who is None:
            self._who = [Writer().bytes(6, a).varint(7, i).finish()
                         for i, a in enumerate(addrs)]
        lanes_l, times_l = lanes.tolist(), times.tolist()
        stamps = [canonical.ts_field_bytes(t) for t in times_l]
        items = []
        for i, ts in zip(lanes_l, stamps):
            body = pre + ts + suf
            items.append((i, encode_varint(len(body)) + body))
        sigs = sign(items)
        msgs = []
        for i, ts, sig in zip(lanes_l, stamps, sigs):
            vote = head + ts + self._who[i] + b"\x42\x40" + sig
            msgs.append(b"\x06\x0a" + encode_varint(len(vote)) + vote)
        first = Vote(vtype, hs.height, 0, hs.block_id, times_l[0],
                     addrs[lanes_l[0]], lanes_l[0], sigs[0])
        if msgs[0] != m.encode_consensus_msg(m.VoteMessage(first)) or \
                items[0][1] != first.sign_bytes(self.chain_id) or \
                len(sigs[0]) != 64:
            raise AssertionError("the spliced vote encoding left the "
                                 "program's")
        hs.lanes[vtype], hs.times[vtype] = lanes, times
        hs.sigs[vtype], hs.msgs[vtype] = sigs, msgs

    def _plant(self, hs, per_1000: float, addrs, rng) -> None:
        if per_1000 <= 0:
            return
        total = hs.signatures()
        count = max(1, round(total * per_1000 / 1000.0))
        for pick in rng.choice(total, count, replace=False).tolist():
            vtype = VOTE_TYPES[0]
            if pick >= len(hs.lanes[vtype]):
                pick -= len(hs.lanes[vtype])
                vtype = VOTE_TYPES[1]
            lane = int(hs.lanes[vtype][pick])
            bad = spoil(hs.sigs[vtype][pick])
            vote = Vote(vtype, hs.height, 0, hs.block_id,
                        int(hs.times[vtype][pick]), addrs[lane], lane, bad)
            hs.planted.append(Planted(
                vtype, lane,
                m.encode_consensus_msg(m.VoteMessage(vote)), bad))

    def at(self, height: int) -> HeightScript | None:
        if 1 <= height <= len(self.heights):
            return self.heights[height - 1]
        return None


# ------------------------------------------------------------ the peers


class _Share:
    """One peer's share of one height's votes of one type: positions
    into the HeightScript's arrays in sending order, and which of them
    it has handed over (its marks, which a VoteSetBits answer
    replaces)."""

    __slots__ = ("order", "sent", "resend", "good_copies")

    def __init__(self, order: list[int], good_copies=()):
        self.order = order
        self.sent: set[int] = set()     # validator indexes marked sent
        self.resend: list[int] = []     # positions to send again
        # positions of the good copies of votes that were planted
        self.good_copies = frozenset(good_copies)


class ScriptedPeer:
    """What the consensus reactor (and the switch's broadcast) sees as
    a peer. Outbound bytes are READ, not kept: NewRoundStep and
    VoteSetBits are decoded on arrival, HasVote is logged raw (one
    entry a vote the node adds, decoded by `ScriptedNet.acknowledged`)
    and everything else is counted."""

    outbound = True
    persistent = False
    socket_addr = ""
    slow_level = 0

    def __init__(self, net: "ScriptedNet", index: int):
        self.net = net
        self.index = index
        node_id = hashlib.sha256(
            f"scripted-peer/{net.chain.seed}/{index}".encode()
        ).hexdigest()[:40]
        self.node_info = NodeInfo(
            node_id=node_id, listen_addr="", network=net.chain.chain_id,
            moniker=f"scripted{index}",
            channels=bytes([STATE_CHANNEL, DATA_CHANNEL, VOTE_CHANNEL,
                            VOTE_SET_BITS_CHANNEL]))
        self._kv: dict[str, object] = {}
        # the node as this peer knows it
        self.height = 0
        self.round = -1
        self.step = 0
        self.has_vote_log: list[bytes] = []
        self.other_sends = 0
        self.shares: dict[tuple[int, VoteType], _Share] = {}
        self.handed_over = 0
        self.redelivered = 0
        self._stepped = asyncio.Event()
        self._tasks: list[asyncio.Task] = []

    # -- the p2p.Peer surface reactors and the switch use --

    @property
    def id(self) -> str:
        return self.node_info.node_id

    def is_persistent(self) -> bool:
        return False

    def get(self, key: str):
        return self._kv.get(key)

    def set(self, key: str, value) -> None:
        self._kv[key] = value

    def pending_send_bytes(self) -> int:
        return 0

    def send_rate(self) -> float:
        return 0.0

    def send_queue_depth(self) -> int:
        return 0

    def send_queue_capacity(self) -> int:
        return 0

    async def start(self) -> None:
        pass

    async def stop(self) -> None:
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        self._tasks.clear()

    async def send(self, chan_id: int, msg: bytes) -> bool:
        return self.try_send(chan_id, msg)

    def try_send(self, chan_id: int, msg: bytes) -> bool:
        tag = msg[0] if msg else 0
        if chan_id == STATE_CHANNEL and tag == _TAG_HAS_VOTE:
            self.has_vote_log.append(msg)
        elif chan_id == STATE_CHANNEL and tag == _TAG_NEW_ROUND_STEP:
            step = m.decode_consensus_msg(msg)
            self.height, self.round, self.step = (
                step.height, step.round, step.step)
            self._stepped.set()
        elif chan_id == VOTE_SET_BITS_CHANNEL and tag == _TAG_VOTE_SET_BITS:
            self._apply_vote_set_bits(m.decode_consensus_msg(msg))
        else:
            self.other_sends += 1
        return True

    # -- behaviour --

    def run(self, reactor) -> None:
        loop = asyncio.get_running_loop()
        self._tasks = [
            loop.create_task(self._stream(reactor),
                             name=f"scripted-stream-{self.index}"),
            loop.create_task(self._query_maj23(reactor),
                             name=f"scripted-maj23-{self.index}"),
        ]

    async def _stream(self, reactor) -> None:
        """Height by height as the node announces them: proposal and
        parts (the dealer), then this peer's prevotes, then its
        precommits, then whatever VoteSetBits answers asked for again."""
        streamed = 0
        while True:
            while self.height <= streamed and not self._resends():
                self._stepped.clear()
                await self._stepped.wait()
            if self.height > streamed:
                hs = self.net.chain.at(self.height)
                streamed = self.height
                if hs is None:
                    continue   # the chain has run out
                await self.net._going.wait()
                if hs.height % len(self.net.peers) == self.index:
                    await reactor.receive(DATA_CHANNEL, self, hs.proposal)
                    for part in hs.part_msgs:
                        await reactor.receive(DATA_CHANNEL, self, part)
                for vtype in VOTE_TYPES:
                    share = self.shares[hs.height, vtype]
                    await self._hand_over(reactor, hs, vtype, share,
                                          share.order)
            for (h, vtype), share in list(self.shares.items()):
                if share.resend:
                    again, share.resend = share.resend, []
                    self.redelivered += len(again)
                    await self._hand_over(reactor, self.net.chain.at(h),
                                          vtype, share, again)

    def _resends(self) -> bool:
        return any(s.resend for s in self.shares.values())

    async def _hand_over(self, reactor, hs, vtype, share, positions):
        msgs, lanes = hs.msgs[vtype], hs.lanes[vtype]
        going = self.net._going
        for pos in positions:
            if not going.is_set():
                await going.wait()
            if pos < 0:     # a planted copy: never marked, never again
                pl = hs.planted[-pos - 1]
                self.net.planted_at.append(
                    (self.net.handed_over(), hs.height, int(vtype),
                     pl.lane, self.id))
                await reactor.receive(VOTE_CHANNEL, self, pl.msg)
            else:
                if pos in share.good_copies:
                    self.net.good_copy_at.setdefault(
                        (hs.height, int(vtype), int(lanes[pos])),
                        len(self.net.peers[0].has_vote_log))
                await reactor.receive(VOTE_CHANNEL, self, msgs[pos])
                share.sent.add(int(lanes[pos]))
            self.handed_over += 1
            # a socket's reader yields between messages; so does this
            await asyncio.sleep(0)

    async def _query_maj23(self, reactor) -> None:
        while True:
            await asyncio.sleep(self.net.query_maj23_s)
            await self.net._going.wait()
            hs = self.net.chain.at(self.height)
            if hs is None:
                continue
            for vtype in VOTE_TYPES:
                await reactor.receive(
                    STATE_CHANNEL, self, m.encode_consensus_msg(
                        m.VoteSetMaj23Message(hs.height, 0, int(vtype),
                                              hs.block_id)))

    def _apply_vote_set_bits(self, msg: m.VoteSetBitsMessage) -> None:
        """The node's self-report replaces this peer's marks for its
        own share (reference ApplyVoteSetBitsMessage): a vote marked
        sent that the node does not report is sent again."""
        hs = self.net.chain.at(msg.height)
        if hs is None or msg.round != 0 or msg.block_id != hs.block_id:
            return
        share = self.shares.get((msg.height, VoteType(msg.type)))
        if share is None:
            return
        lanes = hs.lanes[VoteType(msg.type)]
        queued = set(share.resend)
        for pos in share.order:
            if pos < 0 or pos in queued:
                continue
            lane = int(lanes[pos])
            if lane in share.sent and not msg.votes.get(lane):
                share.sent.discard(lane)
                share.resend.append(pos)
        if share.resend:
            self._stepped.set()


class HeldVotes:
    """What the node held at the end of each height, read through its
    own broadcast hook (`ConsensusState.broadcast_hooks`): the prevote
    set from its commit on, the precommit set — late votes taken in
    NewHeight and after included — from the next height's propose on.
    Each is the set's LIVE bit array, so it ends as the node left it
    when it let the set go (a prevote tallied while the block is being
    applied, a precommit taken into the LastCommit during the next
    height: acknowledged after the step that first showed the set);
    `members` turns one into indexes. Of the validators of `chain`'s
    planted votes it also keeps the SIGNATURE the set holds, under
    (height, type, index), read when the node lets the set go or when
    `signatures` is asked for: the spoiled one there is a vote that
    was tallied unverified."""

    def __init__(self, cs, chain: "ScriptedChain | None" = None):
        self.prevotes: dict[int, object] = {}
        self.precommits: dict[int, object] = {}
        self.rounds: dict[int, int] = {}     # the round each committed in
        self._signatures: dict[tuple[int, int, int], bytes] = {}
        self._live: dict = {}     # vote type -> (height, the node's set)
        self._chain = chain
        cs.broadcast_hooks.append(self._on_event)

    def _on_event(self, event: str, rs) -> None:
        if event != "step":
            return
        if rs.step == RoundStep.COMMIT:
            votes = rs.votes.prevotes(rs.commit_round)
            self.prevotes[rs.height] = votes.votes_bit_array
            self.rounds[rs.height] = rs.commit_round
            self._watch(VoteType.PREVOTE, rs.height, votes)
        elif rs.step == RoundStep.PROPOSE and rs.round == 0 \
                and rs.last_commit is not None:
            self.precommits[rs.height - 1] = rs.last_commit.votes_bit_array
            self._watch(VoteType.PRECOMMIT, rs.height - 1, rs.last_commit)

    def _watch(self, vtype, height: int, votes) -> None:
        """`votes` is the node's set of this type from now on; the one
        before it is let go: read its planted votes' signatures."""
        self._keep_planted(vtype)
        self._live[vtype] = (height, votes)

    def _keep_planted(self, vtype) -> None:
        height, votes = self._live.get(vtype, (0, None))
        hs = self._chain.at(height) if self._chain is not None else None
        for pl in hs.planted if hs is not None else ():
            vote = votes.get_by_index(pl.lane) if pl.type == vtype else None
            if vote is not None:
                self._signatures[height, int(vtype), pl.lane] = \
                    vote.signature

    @property
    def signatures(self) -> dict[tuple[int, int, int], bytes]:
        for vtype in VOTE_TYPES:     # the sets the node still holds
            self._keep_planted(vtype)
        return self._signatures

    def spoiled(self) -> list[tuple[int, int, int]]:
        """The planted votes whose spoiled signature the node held."""
        held = self.signatures
        return [(hs.height, int(pl.type), pl.lane)
                for hs in self._chain.heights for pl in hs.planted
                if held.get(
                    (hs.height, int(pl.type), pl.lane)) == pl.signature]

    @staticmethod
    def members(bits) -> set[int]:
        return {i for i in range(bits.size) if bits.get(i)}


class ScriptedNet:
    """`peers` scripted peers that between them hold a ScriptedChain:
    each vote is dealt to ONE of them by the seed, a planted copy to
    another one, a quarter into its share of that step (the good copy
    goes to the tail of its own peer's). Not at the head: a peer whose
    first verified vote is bad has a trust score of 0, and the node
    disconnects it (behaviour.py) with all it had to bring."""

    def __init__(self, chain: ScriptedChain, peers: int, *,
                 query_maj23_s: float = 2.0):
        self.chain = chain
        self.query_maj23_s = query_maj23_s
        self.peers = [ScriptedPeer(self, i) for i in range(peers)]
        self.reactor = None
        # every spoiled copy handed over: (how many votes the peers had
        # handed over before it, height, type, validator index, peer id)
        self.planted_at: list[tuple] = []
        # (height, type, validator index) of a planted vote -> how many
        # HasVotes the node had sent when its GOOD copy was first
        # handed over
        self.good_copy_at: dict[tuple[int, int, int], int] = {}
        self._has_vote_seen: list[tuple[int, int, int, int]] = []
        self._going = asyncio.Event()   # cleared while paused
        self._going.set()
        rng = np.random.default_rng([chain.seed, 0xDEA1])
        for hs in chain.heights:
            for vtype in VOTE_TYPES:
                self._deal(hs, vtype, rng)

    def _deal(self, hs: HeightScript, vtype, rng) -> None:
        n = len(hs.lanes[vtype])
        P = len(self.peers)
        owner = rng.integers(0, P, n)
        order = rng.permutation(n)
        planted = {int(np.searchsorted(hs.lanes[vtype], p.lane)): k
                   for k, p in enumerate(hs.planted) if p.type == vtype}
        heads: list[list[int]] = [[] for _ in range(P)]
        body: list[list[int]] = [[] for _ in range(P)]
        tails: list[list[int]] = [[] for _ in range(P)]
        for pos in order.tolist():
            k = planted.get(pos)
            if k is None:
                body[owner[pos]].append(pos)
                continue
            tails[owner[pos]].append(pos)
            other = (int(owner[pos]) + 1
                     + int(rng.integers(0, max(1, P - 1)))) % P
            heads[other].append(-k - 1)
        for peer, a, b, c in zip(self.peers, heads, body, tails):
            cut = len(b) // 4
            peer.shares[hs.height, vtype] = _Share(
                b[:cut] + a + b[cut:] + c, good_copies=c)

    async def attach(self, switch, reactor) -> None:
        """Connect every peer: the switch's table (its broadcasts reach
        them) and the reactor's add_peer, which starts the node's own
        gossip routines for each."""
        self.reactor = reactor
        for peer in self.peers:
            switch.peers[peer.id] = peer
            await reactor.add_peer(peer)
            peer.run(reactor)

    def pause(self) -> None:
        """The peers hand over nothing more (a message already in the
        reactor's hands is finished) and stay connected: the node keeps
        what it has and can be read. On the peers' loop."""
        self._going.clear()

    def resume(self) -> None:
        self._going.set()

    async def stop(self, switch=None) -> None:
        for peer in self.peers:
            await peer.stop()
            if self.reactor is not None:
                await self.reactor.remove_peer(peer, "scripted net stopped")
            if switch is not None:
                switch.peers.pop(peer.id, None)

    # -- what the node acknowledged --

    def acknowledged(self) -> dict[tuple[int, int], set[int]]:
        """{(height, vote type): validator indexes} of the HasVote
        messages the node broadcast: one for every vote it verified
        and added. Every peer is sent the same; the first one's log is
        read."""
        out: dict[tuple[int, int], set[int]] = {}
        for height, round_, vtype, index in self._has_votes():
            if round_ == 0:
                out.setdefault((height, vtype), set()).add(index)
        return out

    def _has_votes(self) -> list[tuple[int, int, int, int]]:
        """(height, round, type, index) of the first peer's HasVote
        log, in order; each message is decoded once."""
        log = self.peers[0].has_vote_log
        for raw in log[len(self._has_vote_seen):]:
            hv = m.decode_consensus_msg(raw)
            self._has_vote_seen.append(
                (hv.height, hv.round, hv.type, hv.index))
        return self._has_vote_seen

    def acknowledged_before_good_copy(self) -> list[tuple[int, int, int]]:
        """The planted votes the node acknowledged (HasVote) BEFORE
        their good copy was handed over: only the spoiled copy can
        have been tallied then."""
        planted = {(hs.height, int(pl.type), pl.lane)
                   for hs in self.chain.heights for pl in hs.planted}
        first: dict[tuple[int, int, int], int] = {}
        for at, (height, _, vtype, index) in enumerate(self._has_votes()):
            if (height, vtype, index) in planted:
                first.setdefault((height, vtype, index), at)
        return sorted(k for k, at in first.items()
                      if at < self.good_copy_at.get(k, at + 1))

    def handed_over(self) -> int:
        return sum(p.handed_over for p in self.peers)

    def redelivered(self) -> int:
        return sum(p.redelivered for p in self.peers)
