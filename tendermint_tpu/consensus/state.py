"""The Tendermint BFT state machine (reference: consensus/state.go:85).

One asyncio task serializes everything (the receiveRoutine analogue,
state.go:686-765): peer messages, internal messages (our own proposals
and votes loop back through the same queue), and timeouts. Every
message that can change state is WAL'd before being acted on; an
EndHeightMessage delimits committed heights for crash recovery.

Transitions (state.go:909-1596):
  NewRound → Propose → Prevote → PrevoteWait → Precommit →
  PrecommitWait → Commit → (apply via BlockExecutor) → NewHeight

Signature verification throughout rides the BatchVerifier surfaces in
types/ (vote_set.py, validator_set.py) — on TPU for wide batches."""

from __future__ import annotations

import asyncio
import time as _time

from ..libs import clock as _clock
from dataclasses import dataclass

from ..config import ConsensusConfig
from ..libs import tracing
from ..libs.failpoints import hit as _failpoint
from ..libs.overload import CONTROLLER, PriorityFunnel
from ..libs.service import Service
from ..mempool import Mempool, NopMempool
from ..state import State as SmState
from ..state.execution import BlockExecutor
from ..store import BlockStore
from ..types.block import Block, BlockID, BlockIDFlag, Commit, NIL_BLOCK_ID, PartSet
from ..types.events import (
    EventBus, EventDataRoundState, EventDataVote,
)
from ..types.priv_validator import PrivValidator
from ..types.proposal import Proposal
from ..types.vote import Vote, VoteType
from ..types.vote_set import ConflictingVoteError, VoteSet, VoteSetError
from . import messages as m
from .cstypes import HeightVoteSet, RoundState, RoundStep
from .ticker import TimeoutTicker
from .wal import (
    EndHeightMessage, MsgInfo, RoundStateMessage, TimeoutInfo, WAL,
)

# The most windows (vote_batch_window_ms each) the vote scheduler holds
# a cut for a burst that is still arriving: 100 ms at the shipped 2 ms,
# a tenth of the shipped timeout_prevote, and what the receive routine
# takes to hand it a batch of vote_batch_max. And how many of them in
# a row may add nothing to the buffer before the burst counts as over
# (one was too few: a peer's re-deliveries come a hundred duplicates
# at a time, and every run of them cut a batch of a few dozen lanes:
# PERF.md §6, PR 39).
_HOLD_WINDOWS = 50
_HOLD_IDLE_WINDOWS = 5


@dataclass
class _QueuedMsg:
    msg: object
    peer_id: str
    raw: bytes | None = None   # the message as it came off the wire
    spent_ns: int = 0   # what the reactor spent on it before the funnel


class ConsensusState(Service):
    # Span handles for the per-height trace timeline. Class-level
    # defaults because update_to_state (which rolls them) runs inside
    # __init__ before any instance attribute could be assigned.
    _ht_span = None
    _step_span = None
    # Node label for height forensics: when non-empty, every height/
    # step span carries node=<label> and outgoing lifecycle messages
    # are origin-stamped with it. Set by the builder (moniker) after
    # construction; "" (the default) disables both — single-node use
    # needs no identity. Class-level for the same __init__ reason.
    trace_node = ""

    def __init__(self, config: ConsensusConfig, state: SmState,
                 block_exec: BlockExecutor, block_store: BlockStore,
                 mempool: Mempool | None = None, evpool=None,
                 wal: WAL | None = None, event_bus: EventBus | None = None,
                 speculation=None):
        super().__init__(name="consensus.State")
        self.config = config
        # Verify-ahead plane (consensus/speculation.py, wired by
        # node._build from [speculation]): fed the proposal BlockID at
        # _set_proposal and every current-height precommit at
        # _add_vote; BlockExecutor serves commit verdicts from it.
        self.speculation = speculation
        self.block_exec = block_exec
        self.block_store = block_store
        self.mempool = mempool or NopMempool()
        self.evpool = evpool
        self.wal = wal
        self.event_bus = event_bus
        self.priv_validator: PrivValidator | None = None
        self.priv_validator_address: bytes | None = None

        self.rs = RoundState()
        self.state: SmState | None = None
        # Priority-split bounded receive funnel (libs/overload.py):
        # state/vote/proposal messages block the sender when full
        # (backpressure, the reference's peerMsgQueue channel send);
        # block parts / catchup data shed when full — a gossip flood
        # must not starve round progression or grow memory unboundedly.
        self.peer_funnel = PriorityFunnel(
            config.peer_funnel_votes_size, config.peer_funnel_data_size,
            high_queue="consensus.funnel.votes",
            low_queue="consensus.funnel.data")
        self.internal_msg_queue: asyncio.Queue[_QueuedMsg] = asyncio.Queue(1000)
        self.ticker = TimeoutTicker()
        self._replay_mode = False
        # Serializes state transitions between the receive routine and
        # the vote micro-batch scheduler (the analogue of reference
        # cs.mtx — asyncio tasks interleave at awaits, and step
        # transitions contain awaits).
        self._state_mtx = asyncio.Lock()
        # Vote micro-batch scheduler buffers (SURVEY §7 latency budget):
        # (vote, peer_id, pub_key) triples awaiting one device batch.
        self._vote_buf: list = []
        self._vote_pending = asyncio.Event()
        CONTROLLER.register("consensus.vote_buf",
                            lambda: len(self._vote_buf),
                            config.vote_buf_max, owner=self)
        self._tpu_metrics = None  # lazy tpu_metrics() handle (hot path)
        # tracing of the vote path, per micro-batch (libs/tracing.py):
        # when the buffer's first vote came, what the bound shed since
        # the last cut, and the two folded per-message sites
        self._vote_first_ns = 0
        self._vote_shed = 0
        # set while no vote is buffered or in a batch (_settle_votes)
        self._vote_idle = asyncio.Event()
        self._vote_idle.set()
        self._height_done = asyncio.Event()  # pulsed on every commit
        # reactor hooks: fn(event_name, payload); events: "step",
        # "proposal", "block_part", "vote", "has_vote", and the
        # maverick split events "vote_split"/"proposal_split"
        self.broadcast_hooks: list = []
        # Maverick hook points (test/maverick analogue): height ->
        # Misbehavior; consulted at enter_propose/prevote/precommit
        # (consensus/misbehavior.py). Empty for honest nodes.
        self.misbehaviors: dict = {}
        # () -> behaviour.SwitchReporter | None; set by the reactor so
        # verified/rejected vote counts feed the peer trust metric.
        self.reporter_fn = lambda: None

        self.update_to_state(state)
        if state.last_block_height > 0:
            self.reconstruct_last_commit()

    # -- wiring --

    def set_priv_validator(self, pv: PrivValidator | None) -> None:
        self.priv_validator = pv
        self.priv_validator_address = (
            pv.get_pub_key().address() if pv is not None else None
        )

    def _broadcast(self, event: str, payload) -> None:
        for hook in self.broadcast_hooks:
            hook(event, payload)

    # -- lifecycle --

    async def on_start(self) -> None:
        if self.wal is not None:
            await self._catchup_replay()
        await self._load_programs()
        self.spawn(self._receive_routine(), name="cs-receive")
        if self.config.vote_batch_window_ms > 0:
            self.spawn(self._vote_scheduler(), name="cs-vote-batch")
        self._schedule_round0()

    async def _load_programs(self) -> None:
        """Before the first vote is taken: the device programs this
        node's live path launches for its validator set, loaded off
        the loop — the set's comb tables and the structured program at
        vote_batch_max lanes (every launch of the vote scheduler and
        of a LastCommit's unserved lanes: ValidatorSet.verify_live)
        and the speculation plane's arena. A set without resident
        tables loads nothing. After this the live path compiles
        nothing, whatever lengths the timing cuts; a node that met a
        cold program in the middle of a round held its executor for
        ~50 s while the timeouts fired (PERF.md §6, PR 40). A load
        that fails is logged and left to the first launch."""
        vals = self.rs.validators
        # (asked here, not in the executor: a set without tables must
        # start without a turn of the loop, as it always has)
        if vals is None or not vals.tables_resident():
            return

        def load() -> int:
            programs = vals.load_live_programs(self.config.vote_batch_max)
            if self.speculation is not None:
                programs += self.speculation.load_programs(vals)
            return programs

        t0 = _time.perf_counter_ns()
        try:
            programs = await asyncio.get_running_loop().run_in_executor(
                None, tracing.TRACER.wrap(load))
        except Exception:
            self.logger.exception("loading the live path's programs "
                                  "failed; the first launches compile")
            return
        if programs:
            seconds = (_time.perf_counter_ns() - t0) / 1e9
            tracing.TRACER.begin(
                tracing.CONSENSUS_LOAD_PROGRAMS, start_ns=t0,
                validators=len(vals), programs=programs,
                seconds=round(seconds, 3)).end()
            self.logger.info("live path: %d programs loaded in %.1f s",
                             programs, seconds)

    async def on_stop(self) -> None:
        self.ticker.stop()
        # drop overload registrations: a stopped node's frozen queue
        # depths must not pin the process-wide level (owner-checked —
        # a newer in-process node's same-name entries survive)
        self.peer_funnel.close()
        CONTROLLER.unregister("consensus.vote_buf", owner=self)
        if self.speculation is not None:
            self.speculation.close()
        if self.wal is not None:
            self.wal.close()

    def _schedule_round0(self) -> None:
        # fire NewHeight immediately (start_time already accounts for
        # timeout_commit when coming off a commit)
        delay = max(self.rs.start_time - _clock.monotonic(), 0.0)
        self.ticker.schedule(TimeoutInfo(
            delay, self.rs.height, 0, int(RoundStep.NEW_HEIGHT)
        ))

    # -- state sync between heights (reference updateToState, state.go:566) --

    def update_to_state(self, state: SmState) -> None:
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height != state.last_block_height:
            raise RuntimeError(
                f"update_to_state height mismatch {rs.height} vs "
                f"{state.last_block_height}"
            )
        last_precommits: VoteSet | None = None
        if rs.commit_round > -1 and rs.votes is not None:
            pc = rs.votes.precommits(rs.commit_round)
            if pc is None or not pc.has_two_thirds_majority():
                raise RuntimeError("commit round has no +2/3 precommits")
            last_precommits = pc

        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height

        validators = state.validators.copy()
        self.rs = RoundState(
            height=height,
            round=0,
            step=RoundStep.NEW_HEIGHT,
            start_time=_clock.monotonic() + (
                self.config.commit_timeout()
                if not self.config.skip_timeout_commit and rs.commit_round > -1
                else 0.0
            ),
            validators=validators,
            votes=HeightVoteSet(state.chain_id, height, validators),
            last_commit=last_precommits,
            last_validators=state.last_validators.copy(),
            commit_round=-1,
            locked_round=-1,
            valid_round=-1,
        )
        self.state = state
        if self.speculation is not None:
            self.speculation.retire_below(height)
        self._trace_new_height(height)

    def _trace_new_height(self, height: int) -> None:
        """Roll the per-height trace timeline: seal the previous
        height's step + root spans, open the next root. Manually
        managed (not a with-block) because a height's lifetime spans
        many handler invocations across two tasks (receive routine and
        vote scheduler)."""
        t = tracing.TRACER
        if self._step_span is not None:
            self._step_span.end()
            self._step_span = None
        if self._ht_span is not None:
            self._ht_span.end()
        # parent=NOOP_SPAN pins the root parentless: update_to_state
        # can run inside the vote scheduler's active vote_batch span,
        # and a height must never parent under a vote batch.
        self._ht_span = t.begin(tracing.CONSENSUS_HEIGHT,
                                parent=tracing.NOOP_SPAN, height=height)
        # RoundStepNewHeight: from here to round 0 the node waits out
        # timeout_commit; the first _new_step (propose) seals it, so
        # the wait and the height's work no longer share one label
        self._step_span = t.begin(tracing.CONSENSUS_NEW_HEIGHT,
                                  parent=self._ht_span, height=height)
        if self.trace_node:
            self._ht_span.set_attr("node", self.trace_node)
            self._step_span.set_attr("node", self.trace_node)

    def reconstruct_last_commit(self) -> None:
        """Rebuild rs.last_commit from the stored seen commit
        (reference state.go:549)."""
        assert self.state is not None
        seen = self.block_store.load_seen_commit(self.state.last_block_height)
        if seen is None:
            raise RuntimeError(
                f"no seen commit for height {self.state.last_block_height}"
            )
        last_precommits = VoteSet(
            self.state.chain_id, seen.height, seen.round,
            VoteType.PRECOMMIT, self.state.last_validators,
        )
        votes = []
        for idx, cs_sig in enumerate(seen.signatures):
            if cs_sig.is_absent():
                continue
            votes.append(Vote(
                type=VoteType.PRECOMMIT,
                height=seen.height,
                round=seen.round,
                block_id=cs_sig.block_id_for(seen.block_id),
                timestamp=cs_sig.timestamp,
                validator_address=cs_sig.validator_address,
                validator_index=idx,
                signature=cs_sig.signature,
            ))
        # One device batch for the whole stored commit instead of a
        # per-signature host loop (this is our own store, but the
        # reference verifies here too — state.go:549 via AddVote).
        from ..crypto.batch import BatchVerifier

        bv = BatchVerifier()
        vals = self.state.last_validators
        for v in votes:
            val = vals.get_by_index(v.validator_index)
            bv.add(val.pub_key, v.sign_bytes(self.state.chain_id), v.signature)
        _, verdicts = bv.verify()
        for v, ok in zip(votes, verdicts):
            if not ok:
                raise RuntimeError(
                    f"invalid signature in seen commit (val index "
                    f"{v.validator_index})"
                )
            last_precommits.add_vote(v, verify=False)
        if not last_precommits.has_two_thirds_majority():
            raise RuntimeError("seen commit lacks +2/3")
        self.rs.last_commit = last_precommits

    # -- the serialized event loop --

    async def _receive_routine(self) -> None:
        now_ns = _time.perf_counter_ns
        sources = (self.internal_msg_queue, self.peer_funnel,
                   self.ticker.queue)
        while True:
            # What is already queued is taken where it lies, one
            # message of each source a turn (our own, then a peer's,
            # then a timeout: a stream of votes never holds a timeout
            # back), with one turn of the loop between two turns; only
            # an idle routine waits on the three sources at once (three
            # tasks made and two cancelled: a third of a vote's cost
            # when that was every message's way in, PERF.md §6, PR 39).
            internal, peer, timeout = (
                q.get_nowait() if q.qsize() else None for q in sources)
            if internal is None and peer is None and timeout is None:
                waits = [asyncio.ensure_future(q.get()) for q in sources]
                try:
                    await asyncio.wait(
                        waits, return_when=asyncio.FIRST_COMPLETED)
                finally:
                    for w in waits:
                        if not w.done():
                            w.cancel()
                internal, peer, timeout = (
                    w.result() if w.done() and not w.cancelled() else None
                    for w in waits)
            else:
                await asyncio.sleep(0)
            traced = tracing.TRACER.enabled   # no clock reads otherwise
            t_woke = now_ns() if traced else 0
            try:
                if internal is not None:
                    qm = internal
                    self._wal_write_sync(MsgInfo(
                        "", m.encode_consensus_msg(qm.msg)
                    ))
                    async with self._state_mtx:
                        await self._handle_msg(qm)
                if peer is not None:
                    qm = peer
                    self._wal_write(MsgInfo(
                        qm.peer_id, qm.raw if qm.raw is not None
                        else m.encode_consensus_msg(qm.msg)
                    ))
                    # one unit of consensus.receive: the reactor's
                    # decode and marks, then this turn of the loop from
                    # its wake: a vote's up to the scheduler's buffer; a
                    # proposal's or a part's up to here, for what it
                    # sets off (a block's validation) is a step's work,
                    # with awaits
                    is_vote = isinstance(qm.msg, m.VoteMessage)
                    if not is_vote:
                        if self._ahead_of_us(qm.msg):
                            await self._settle_votes()
                            t_woke = now_ns() if traced else 0
                        if traced:
                            self._trace_received(qm, t_woke)
                    async with self._state_mtx:
                        await self._handle_msg(qm)
                    if is_vote and traced:
                        self._trace_received(qm, t_woke)
                if timeout is not None:
                    ti = timeout
                    self._wal_write_sync(ti)
                    async with self._state_mtx:
                        await self._handle_timeout(ti)
            except asyncio.CancelledError:
                raise
            except Exception:
                self.logger.exception("consensus handler failed; halting")
                raise

    def _trace_received(self, qm: _QueuedMsg, t_woke: int) -> None:
        """One unit of the folded consensus.receive: [t_woke, now)
        and, before it, what the reactor spent on the message."""
        tracing.TRACER.leaf(
            tracing.CONSENSUS_RECEIVE, t_woke - qm.spent_ns,
            fold_key=(tracing.CONSENSUS_RECEIVE, id(self)),
            parent=self._ht_span,
            decode_ms=qm.spent_ns / 1e6,
            shaped=int(getattr(qm.msg, "shaped", False)))

    def _ahead_of_us(self, msg) -> bool:
        height = msg.proposal.height if isinstance(
            msg, m.ProposalMessage) else getattr(msg, "height", 0)
        return height > self.rs.height

    async def _settle_votes(self) -> None:
        """Hold the intake until every vote taken off the funnel so
        far has its verdict and is tallied. The funnel hands messages
        over in the order they came, but a vote goes on through the
        scheduler (a window, a launch, the tally) while a proposal or
        a part is handled at once: the next height's proposal can
        overtake the precommits that end this one, find the node a
        height behind and be dropped, to come again only by gossip.
        Asked for just then: a message ahead of our height. (The
        scheduler's hold ends once its buffer has stood still for
        _HOLD_IDLE_WINDOWS windows, so an intake that waits here
        ends it.)"""
        await self._vote_idle.wait()

    def _wal_write(self, msg) -> None:
        if self.wal is not None and not self._replay_mode:
            self.wal.write(msg, _clock.time_ns())

    def _wal_write_sync(self, msg) -> None:
        if self.wal is not None and not self._replay_mode:
            self.wal.write_sync(msg, _clock.time_ns())

    async def _handle_msg(self, qm: _QueuedMsg) -> None:
        """Validation failures on a single message are logged and
        dropped — one byzantine peer must not halt the node (reference
        handleMsg logs setProposal/AddProposalBlockPart errors and
        continues). Errors inside step *transitions* still propagate:
        those are local invariant violations (reference panics →
        graceful halt)."""
        msg = qm.msg
        if isinstance(msg, m.ProposalMessage):
            try:
                self._set_proposal(msg.proposal)
            except Exception as e:
                self.logger.warning("rejecting proposal from %r: %s",
                                    qm.peer_id, e)
                return
            # parts may have completed before the proposal arrived
            if self.rs.proposal_complete():
                await self._proposal_completed()
        elif isinstance(msg, m.BlockPartMessage):
            try:
                added = self._add_proposal_block_part(msg)
            except Exception as e:
                self.logger.warning("rejecting block part from %r: %s",
                                    qm.peer_id, e)
                return
            if added and self.rs.step == RoundStep.COMMIT and \
                    self.rs.proposal_block is not None:
                # catchup: block completed while waiting in commit with
                # no Proposal (reference addProposalBlockPart →
                # tryFinalizeCommit when cs.Step == RoundStepCommit)
                await self._try_finalize_commit(self.rs.height)
            elif added and self.rs.proposal_complete():
                await self._proposal_completed()
        elif isinstance(msg, m.VoteMessage):
            if (self._replay_mode or self.config.vote_batch_window_ms <= 0
                    or not self._enqueue_vote(msg.vote, qm.peer_id)):
                await self._try_add_vote(msg.vote, qm.peer_id)
        else:
            self.logger.warning("unknown consensus msg %r", type(msg))

    async def _handle_timeout(self, ti: TimeoutInfo) -> None:
        rs = self.rs
        if ti.height != rs.height or ti.round < rs.round or (
            ti.round == rs.round and ti.step < int(rs.step)
        ):
            return  # stale
        step = RoundStep(ti.step)

        def fire(publisher_name):  # reference state.go:854-864
            if self.event_bus is not None:
                getattr(self.event_bus, publisher_name)(
                    EventDataRoundState(ti.height, ti.round, step.name))

        if step == RoundStep.NEW_HEIGHT:
            await self._enter_new_round(ti.height, 0)
        elif step == RoundStep.NEW_ROUND:
            await self._enter_propose(ti.height, 0)
        elif step == RoundStep.PROPOSE:
            fire("publish_timeout_propose")
            await self._enter_prevote(ti.height, ti.round)
        elif step == RoundStep.PREVOTE_WAIT:
            fire("publish_timeout_wait")
            await self._enter_precommit(ti.height, ti.round)
        elif step == RoundStep.PRECOMMIT_WAIT:
            fire("publish_timeout_wait")
            await self._enter_precommit(ti.height, ti.round)
            await self._enter_new_round(ti.height, ti.round + 1)

    # -- step transitions --

    def _new_step(self, step: RoundStep) -> None:
        self.rs.step = step
        if self._step_span is not None:
            self._step_span.end()
        self._step_span = tracing.TRACER.begin(
            tracing.consensus_step_kind(step.name), parent=self._ht_span,
            height=self.rs.height, round=self.rs.round)
        if self.trace_node:
            self._step_span.set_attr("node", self.trace_node)
        rsm = RoundStateMessage(self.rs.height, self.rs.round, int(step))
        self._wal_write(rsm)
        if self.event_bus is not None:
            self.event_bus.publish_new_round_step(EventDataRoundState(
                self.rs.height, self.rs.round, step.name
            ))
        self._broadcast("step", self.rs)

    async def _enter_new_round(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step != RoundStep.NEW_HEIGHT
        ):
            return
        if round_ > rs.round and rs.validators is not None:
            # advance proposer rotation for skipped rounds
            rs.validators.increment_proposer_priority(round_ - rs.round)
        rs.round = round_
        rs.step = RoundStep.NEW_ROUND
        if round_ > 0:
            # new round: prior proposal is void
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round_)
        rs.triggered_timeout_precommit = False
        if self.event_bus is not None:
            self.event_bus.publish_new_round(EventDataRoundState(
                height, round_, rs.step.name
            ))
        await self._enter_propose(height, round_)

    async def _enter_propose(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= RoundStep.PROPOSE
        ):
            return
        rs.round = round_
        self._new_step(RoundStep.PROPOSE)

        self.ticker.schedule(TimeoutInfo(
            self.config.propose_timeout(round_), height, round_,
            int(RoundStep.PROPOSE),
        ))

        mb = self.misbehaviors.get(height)
        if mb is not None and await mb.enter_propose(self, height, round_):
            return

        if self._is_proposer() and self.priv_validator is not None:
            await self._decide_proposal(height, round_)

        if rs.proposal_complete():
            await self._enter_prevote(height, round_)

    def _is_proposer(self) -> bool:
        return (
            self.priv_validator_address is not None
            and self.rs.validators is not None
            and self.rs.validators.get_proposer().address
            == self.priv_validator_address
        )

    async def _decide_proposal(self, height: int, round_: int) -> None:
        """reference defaultDecideProposal (state.go:1063)."""
        rs = self.rs
        if rs.valid_block is not None:
            block, parts = rs.valid_block, rs.valid_block_parts
        else:
            commit = None
            if height == self.state.initial_height:
                commit = Commit(0, 0, NIL_BLOCK_ID, [])
            elif rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
                commit = rs.last_commit.make_commit()
            else:
                self.logger.error("cannot propose: no last commit")
                return
            block = self.block_exec.create_proposal_block(
                height, self.state, commit, self.priv_validator_address,
            )
            parts = block.make_part_set()

        block_id = BlockID(block.hash(), parts.header())
        proposal = Proposal(
            height=height, round=round_, pol_round=rs.valid_round,
            block_id=block_id, timestamp=_clock.time_ns(),
        )
        try:
            res = self.priv_validator.sign_proposal(self.state.chain_id,
                                                    proposal)
            if asyncio.iscoroutine(res):
                await res  # remote signer round-trip
        except Exception as e:
            self.logger.error("failed to sign proposal: %r", e)
            return
        # Forensics anchor: this node built the block for this round.
        # The collector picks the proposer's propose span by this attr.
        if self._step_span is not None:
            self._step_span.set_attr("proposer", True)
        self._send_internal(m.ProposalMessage(proposal))
        for i in range(parts.total):
            self._send_internal(m.BlockPartMessage(height, round_,
                                                   parts.get_part(i)))

    def _send_internal(self, msg) -> None:
        self.internal_msg_queue.put_nowait(_QueuedMsg(msg, ""))

    async def _proposal_completed(self) -> None:
        """Block fully received: react based on the current step
        (reference addProposalBlockPart, state.go:1775-1840)."""
        rs = self.rs
        prevotes = rs.votes.prevotes(rs.round)
        bid, has_maj = (prevotes.two_thirds_majority()
                        if prevotes is not None else (None, False))
        if has_maj and bid is not None and not bid.is_nil() and rs.valid_round < rs.round:
            if rs.proposal_block.hash() == bid.hash:
                rs.valid_round = rs.round
                rs.valid_block = rs.proposal_block
                rs.valid_block_parts = rs.proposal_block_parts
                if self.event_bus is not None:  # state.go:1450
                    self.event_bus.publish_valid_block(
                        EventDataRoundState(rs.height, rs.round,
                                            rs.step.name))
        if rs.step <= RoundStep.PROPOSE and rs.proposal_complete():
            await self._enter_prevote(rs.height, rs.round)
            if has_maj:
                await self._enter_precommit(rs.height, rs.round)
        elif rs.step == RoundStep.COMMIT:
            await self._try_finalize_commit(rs.height)

    async def _enter_prevote(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= RoundStep.PREVOTE
        ):
            return
        self._new_step(RoundStep.PREVOTE)
        mb = self.misbehaviors.get(height)
        if mb is not None and await mb.enter_prevote(self, height, round_):
            return
        # reference defaultDoPrevote (state.go:1229)
        if rs.locked_block is not None:
            await self._sign_add_vote(VoteType.PREVOTE, rs.locked_block.hash(),
                                rs.locked_block_parts.header())
        elif rs.proposal_block is None:
            await self._sign_add_vote(VoteType.PREVOTE, b"", None)
        else:
            try:
                await self.block_exec.validate_block_async(
                    self.state, rs.proposal_block,
                    self.config.vote_batch_max)
                await self._sign_add_vote(
                    VoteType.PREVOTE, rs.proposal_block.hash(),
                    rs.proposal_block_parts.header(),
                )
            except Exception as e:
                self.logger.warning("invalid proposal block: %r", e)
                await self._sign_add_vote(VoteType.PREVOTE, b"", None)

    async def _enter_prevote_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= RoundStep.PREVOTE_WAIT
        ):
            return
        self._new_step(RoundStep.PREVOTE_WAIT)
        self.ticker.schedule(TimeoutInfo(
            self.config.prevote_timeout(round_), height, round_,
            int(RoundStep.PREVOTE_WAIT),
        ))

    async def _enter_precommit(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step >= RoundStep.PRECOMMIT
        ):
            return
        self._new_step(RoundStep.PRECOMMIT)
        mb = self.misbehaviors.get(height)
        if mb is not None and await mb.enter_precommit(self, height, round_):
            return
        prevotes = rs.votes.prevotes(round_)
        bid, has_maj = (prevotes.two_thirds_majority()
                        if prevotes is not None else (None, False))

        if not has_maj:
            # no polka: precommit nil
            await self._sign_add_vote(VoteType.PRECOMMIT, b"", None)
            return

        if self.event_bus is not None:
            self.event_bus.publish_polka(EventDataRoundState(
                height, round_, rs.step.name
            ))

        if bid is None or bid.is_nil():
            # +2/3 prevoted nil: unlock and precommit nil (state.go:1320)
            if rs.locked_block is not None and self.event_bus is not None:
                self.event_bus.publish_unlock(EventDataRoundState(
                    height, round_, rs.step.name))
            rs.locked_round = -1
            rs.locked_block = None
            rs.locked_block_parts = None
            await self._sign_add_vote(VoteType.PRECOMMIT, b"", None)
            return

        # +2/3 for a block
        if rs.locked_block is not None and rs.locked_block.hash() == bid.hash:
            rs.locked_round = round_  # re-lock at this round
            if self.event_bus is not None:  # state.go:1327
                self.event_bus.publish_relock(EventDataRoundState(
                    height, round_, rs.step.name))
            await self._sign_add_vote(VoteType.PRECOMMIT, bid.hash,
                                bid.part_set_header)
            return
        if rs.proposal_block is not None and rs.proposal_block.hash() == bid.hash:
            try:
                await self.block_exec.validate_block_async(
                    self.state, rs.proposal_block,
                    self.config.vote_batch_max)
            except Exception as e:
                self.logger.error("polka for invalid block: %r", e)
                await self._sign_add_vote(VoteType.PRECOMMIT, b"", None)
                return
            rs.locked_round = round_
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            if self.event_bus is not None:
                self.event_bus.publish_lock(EventDataRoundState(
                    height, round_, rs.step.name
                ))
            await self._sign_add_vote(VoteType.PRECOMMIT, bid.hash,
                                bid.part_set_header)
            return

        # polka for a block we don't have: unlock, precommit nil, fetch
        if rs.locked_block is not None and self.event_bus is not None:
            self.event_bus.publish_unlock(EventDataRoundState(
                height, round_, rs.step.name))  # state.go:1362
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
            bid.part_set_header
        ):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet(
                bid.part_set_header.total, bid.part_set_header.hash
            )
        await self._sign_add_vote(VoteType.PRECOMMIT, b"", None)

    async def _enter_precommit_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.triggered_timeout_precommit
        ):
            return
        rs.triggered_timeout_precommit = True
        self.ticker.schedule(TimeoutInfo(
            self.config.precommit_timeout(round_), height, round_,
            int(RoundStep.PRECOMMIT_WAIT),
        ))

    async def _enter_commit(self, height: int, commit_round: int) -> None:
        rs = self.rs
        if rs.height != height or rs.step >= RoundStep.COMMIT:
            return
        rs.commit_round = commit_round
        rs.commit_time = _clock.monotonic()
        # Forensics anchor: the instant the precommit quorum landed
        # here (enter_commit fires exactly on +2/3). Stamped on the
        # height root so the collector reads it without span joins.
        if self._ht_span is not None:
            self._ht_span.set_attr("precommit_quorum_ns",
                                   _time.perf_counter_ns())
        self._new_step(RoundStep.COMMIT)

        precommits = rs.votes.precommits(commit_round)
        bid, ok = precommits.two_thirds_majority()
        assert ok and bid is not None and not bid.is_nil()

        # if we have the block locked, promote it to proposal slots
        if rs.locked_block is not None and rs.locked_block.hash() == bid.hash:
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts
        # if we don't have the full block yet, set up parts to receive it
        if rs.proposal_block is None or rs.proposal_block.hash() != bid.hash:
            if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                bid.part_set_header
            ):
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet(
                    bid.part_set_header.total, bid.part_set_header.hash
                )
                # advertise which part-set we now accept so peers'
                # catchup gossip starts feeding us the block
                # (reference enterCommit → PublishEventValidBlock →
                # reactor broadcasts NewValidBlockMessage)
                self._broadcast("valid_block", rs)
        await self._try_finalize_commit(height)

    async def _try_finalize_commit(self, height: int) -> None:
        rs = self.rs
        if rs.height != height:
            return
        precommits = rs.votes.precommits(rs.commit_round)
        bid, ok = precommits.two_thirds_majority()
        if not ok or bid is None or bid.is_nil():
            return
        if rs.proposal_block is None or rs.proposal_block.hash() != bid.hash:
            return  # don't have the block yet
        await self._finalize_commit(height)

    async def _finalize_commit(self, height: int) -> None:
        """reference finalizeCommit (state.go:1491)."""
        rs = self.rs
        precommits = rs.votes.precommits(rs.commit_round)
        bid, _ = precommits.two_thirds_majority()
        block, parts = rs.proposal_block, rs.proposal_block_parts

        block.validate_basic()

        # Explicit trace handoff: finalize can run from the receive
        # routine OR the vote scheduler task, so the commit step span
        # is attached by handle (not ambient context) — wal.fsync and
        # state.apply_block below then nest under it either way.
        with tracing.TRACER.attach(self._step_span):
            await self._finalize_commit_traced(height, bid, block, parts,
                                               precommits)

    async def _finalize_commit_traced(self, height, bid, block, parts,
                                      precommits) -> None:
        rs = self.rs
        if self.block_store.height < block.header.height:
            seen_commit = precommits.make_commit()
            self.block_store.save_block(block, parts, seen_commit)

        _failpoint("consensus.commit.block_saved")

        if self.wal is not None and not self._replay_mode:
            self.wal.write_sync(EndHeightMessage(height), _clock.time_ns())

        _failpoint("consensus.commit.wal_delimited")

        state_copy = self.state.copy()
        new_state, retain_height = await self.block_exec.apply_block(
            state_copy, bid, block, self.config.vote_batch_max
        )
        if retain_height > 0:
            try:
                pruned = self.block_store.prune_blocks(retain_height)
                self.block_exec.store.prune_states(1, retain_height)
                self.logger.debug("pruned %d blocks to %d", pruned, retain_height)
            except Exception as e:
                self.logger.error("prune failed: %r", e)

        self._record_commit_metrics(block, precommits,
                                    rs.proposal_block_parts)
        self.update_to_state(new_state)
        self._height_done.set()
        self._height_done = asyncio.Event()
        self._schedule_round0()

    def _record_commit_metrics(self, block, precommits, parts=None) -> None:
        """reference consensus/metrics.go recording (state.go:1612
        recordMetrics)."""
        from ..libs.metrics import consensus_metrics

        met = consensus_metrics()
        met.height.set(block.header.height)
        met.rounds.set(self.rs.round)
        vals = self.rs.validators
        met.validators.set(len(vals))
        met.validators_power.set(vals.total_voting_power())
        missing = missing_power = 0
        for i in range(len(vals)):
            if precommits.get_by_index(i) is None:
                missing += 1
                missing_power += vals.validators[i].voting_power
        met.missing_validators.set(missing)
        met.missing_validators_power.set(missing_power)
        # evidence in THIS block tallies byzantine signers (set
        # unconditionally: the gauges must drop back to 0 on
        # evidence-free blocks, like the reference's)
        byz = {e.vote_a.validator_address
               for e in block.evidence.evidence
               if hasattr(e, "vote_a")}
        met.byzantine_validators.set(len(byz))
        met.byzantine_validators_power.set(sum(
            v.voting_power for v in vals.validators
            if v.address in byz))
        if self.priv_validator_address is not None and \
                vals.has_address(self.priv_validator_address):
            idx, own = vals.get_by_address(self.priv_validator_address)
            met.validator_power.set(own.voting_power)
            if precommits.get_by_index(idx) is not None:
                met.validator_last_signed_height.set(block.header.height)
            else:
                met.validator_missed_blocks.inc()
        ntx = len(block.data.txs)
        met.num_txs.set(ntx)
        met.total_txs.inc(ntx)
        # The part set already holds the serialized size — re-encoding
        # the whole block here would add avoidable per-commit latency.
        if parts is not None:
            met.block_size_bytes.set(parts.byte_size)
        prev = self.block_store.load_block_meta(block.header.height - 1)
        if prev is not None:
            met.block_interval_seconds.observe(
                max(block.header.time - prev.header.time, 0) / 1e9
            )

    # -- proposals & parts --

    def _set_proposal(self, proposal: Proposal) -> None:
        """reference defaultSetProposal (state.go:1719)."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        proposal.validate_basic()
        if proposal.pol_round != -1 and not (
            0 <= proposal.pol_round < proposal.round
        ):
            raise VoteSetError("invalid POL round")
        proposer = rs.validators.get_proposer()
        if not proposer.pub_key.verify_signature(
            proposal.sign_bytes(self.state.chain_id), proposal.signature
        ):
            raise VoteSetError("invalid proposal signature")
        rs.proposal = proposal
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet(
                proposal.block_id.part_set_header.total,
                proposal.block_id.part_set_header.hash,
            )
        if self.speculation is not None:
            # the precommit sign-byte template for this height is now
            # fully determined — start the verify-ahead pipeline
            self.speculation.begin_height(
                self.state.chain_id, rs.validators, rs.height,
                proposal.round, proposal.block_id)
        self._broadcast("proposal", proposal)

    def _add_proposal_block_part(self, msg: m.BlockPartMessage) -> bool:
        rs = self.rs
        if msg.height != rs.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        added = rs.proposal_block_parts.add_part(msg.part)
        if added:
            from ..libs.metrics import consensus_metrics

            consensus_metrics().block_parts.inc()
        if added and rs.proposal_block_parts.is_complete():
            data = rs.proposal_block_parts.assemble()
            block = Block.from_bytes(data)
            # The part-set header (each part merkle-proven into it) is
            # the authoritative identity of what we accepted. Compare
            # against the proposal only when the proposal refers to
            # THIS part set: during commit-time catch-up the parts
            # carry the DECIDED block (header installed by
            # _enter_commit from the +2/3 block id), which legitimately
            # differs from a stale earlier-round proposal — rejecting
            # it wedged a late-joining node behind a racing net for
            # good (found by the statesync e2e under suite load).
            if (rs.proposal is not None and
                    rs.proposal_block_parts.has_header(
                        rs.proposal.block_id.part_set_header) and
                    block.hash() != rs.proposal.block_id.hash):
                raise VoteSetError(
                    "completed block hash != proposal block id")
            rs.proposal_block = block
            # Forensics anchor: first full part set on this node (the
            # proposer hits it too, via its own internal loopback).
            prior = getattr(self._ht_span, "attrs", None) or {}
            if self._ht_span is not None and "parts_complete_ns" not in prior:
                self._ht_span.set_attr("parts_complete_ns",
                                       _time.perf_counter_ns())
            if self.event_bus is not None:
                self.event_bus.publish_complete_proposal(EventDataRoundState(
                    rs.height, rs.round, "CompleteProposal"
                ))
            self._broadcast("block_part", msg)
        elif added:
            self._broadcast("block_part", msg)
        return added

    # -- votes --

    # -- vote micro-batch scheduler --
    #
    # The TPU latency-budget restructuring SURVEY §7 names: votes are
    # not verified one-at-a-time under the VoteSet lock (reference
    # vote_set.go:203); they accumulate for vote_batch_window_ms (or
    # until vote_batch_max) and verify as ONE device batch in a worker
    # thread, then commit under the state mutex with verify=False.
    # Duplicate/conflict semantics are preserved because add_vote
    # re-runs every non-signature check at commit time; the pubkey each
    # lane was verified against is resolved per (height, index), and a
    # height's validator mapping never changes, so a vote cannot be
    # committed against a different key than it was verified with.

    def _enqueue_vote(self, vote: Vote, peer_id: str) -> bool:
        """True if the vote was queued for batch verification (or is a
        known gossip duplicate); False -> caller takes the sync path."""
        resolved = self._resolve_vote_pubkey(vote)
        if resolved is None:
            return False
        pk, vals = resolved
        vs = self._target_vote_set(vote)
        if vs is not None and vs.is_duplicate(vote):
            return True  # already tallied; don't burn a device lane
        if len(self._vote_buf) >= self.config.vote_buf_max:
            if not peer_id:
                # our OWN vote (internal loopback): no peer holds it,
                # so a shed here would silently skip our prevote/
                # precommit for the round — take the sync path instead
                return False
            # Bounded scheduler buffer: shedding a PEER vote (not the
            # sync path — seconds of on-loop crypto is the failure
            # mode this exists to prevent) is safe because gossip
            # re-sends votes the votebits reconciliation shows we
            # still lack.
            CONTROLLER.shed("consensus.vote_buf")
            self._vote_shed += 1
            self._vote_pending.set()  # make sure the drain is awake
            return True
        # vals rides along so the scheduler can route the batch
        # through the expanded structured path (validator-index lanes
        # against the SAME set pk was resolved from).
        if not self._vote_buf:
            self._vote_first_ns = _time.perf_counter_ns()
            self._vote_idle.clear()
        self._vote_buf.append((vote, peer_id, pk, vals))
        m = self._tpu_metrics
        if m is None:
            from ..libs.metrics import tpu_metrics

            self._tpu_metrics = m = tpu_metrics()
        m.verify_queue_depth.set(len(self._vote_buf))
        self._vote_pending.set()
        return True

    def _target_vote_set(self, vote: Vote):
        rs = self.rs
        if vote.height + 1 == rs.height and vote.type == VoteType.PRECOMMIT:
            return rs.last_commit
        if vote.height == rs.height and rs.votes is not None:
            return (rs.votes.prevotes(vote.round)
                    if vote.type == VoteType.PREVOTE
                    else rs.votes.precommits(vote.round))
        return None

    def _resolve_vote_pubkey(self, vote: Vote):
        """(pubkey, validator_set) this vote must verify against, or
        None if it is not addressable right now (wrong height, unknown
        index...) — such votes take the synchronous path, which
        rejects them cheaply before any signature work."""
        rs = self.rs
        if vote.height + 1 == rs.height and vote.type == VoteType.PRECOMMIT:
            vals = (rs.last_commit.val_set
                    if rs.last_commit is not None else None)
        elif vote.height == rs.height:
            vals = rs.validators
        else:
            return None
        if vals is None:
            return None
        val = vals.get_by_index(vote.validator_index)
        if val is None or val.address != vote.validator_address:
            return None
        return val.pub_key, vals

    async def _vote_scheduler(self) -> None:
        from ..libs.metrics import consensus_metrics, tpu_metrics

        met = consensus_metrics()
        tmet = tpu_metrics()
        loop = asyncio.get_running_loop()

        def full() -> bool:
            # Early flush under pressure: once the buffer passes half
            # its bound, waiting only deepens the backlog (and the
            # shedding it causes) — verify NOW.
            return (len(self._vote_buf) >= self.config.vote_batch_max
                    or len(self._vote_buf) * 2 >= self.config.vote_buf_max)

        while True:
            if not self._vote_buf:
                self._vote_idle.set()   # the last batch is tallied
            await self._vote_pending.wait()
            t_window = _time.perf_counter()
            window = self.config.vote_batch_window_ms / 1e3
            cut_by = "full" if full() else "idle"
            if window > 0 and not full():
                # A burst still arriving: votes queued in the funnel
                # behind the receive routine. One window catches what
                # that routine handles in 2 ms — a dozen votes of a
                # 10,000-validator step, under the device threshold,
                # so every batch was the host's and a height 20,000
                # thread hops (PERF.md §6, PR 39). So the cut is held,
                # window by window, while the funnel has votes and the
                # batch is not full, _HOLD_WINDOWS at most, and no
                # longer than _HOLD_IDLE_WINDOWS in a row that added
                # nothing to the buffer: a stream of votes that add
                # nothing (another height's, duplicates of what is
                # tallied) and an intake that waits for this very
                # batch (_settle_votes) end the hold within 10 ms, and
                # a trickle cannot stretch it past a tenth of a step's
                # timeout.
                idle = 0
                for _ in range(_HOLD_WINDOWS):
                    seen = len(self._vote_buf)
                    await asyncio.sleep(window)
                    idle = 0 if len(self._vote_buf) > seen else idle + 1
                    if full():
                        cut_by = "full"
                        break
                    if not self.peer_funnel.high_depth() \
                            or idle >= _HOLD_IDLE_WINDOWS:
                        break
                else:
                    cut_by = "cap"
            # vote_batch_max lanes a launch and no more: the rest of a
            # deeper buffer is the next batch's, at once (the launch
            # shapes a node meets then end at the bucket of
            # vote_batch_max, and a tally holds the loop for one
            # batch's worth of votes)
            cut = self.config.vote_batch_max
            batch, self._vote_buf = self._vote_buf[:cut], \
                self._vote_buf[cut:]
            shed, self._vote_shed = self._vote_shed, 0
            tmet.verify_queue_depth.set(len(self._vote_buf))
            first_ns = self._vote_first_ns
            if self._vote_buf:
                self._vote_first_ns = _time.perf_counter_ns()
            else:
                self._vote_pending.clear()
            if not batch:
                continue
            tracing.TRACER.begin(
                tracing.CONSENSUS_VOTE_QUEUE_WAIT, parent=self._ht_span,
                start_ns=first_ns, lanes=len(batch), shed=shed,
                cut=cut_by).end()
            met.vote_batch_wait_seconds.observe(
                _time.perf_counter() - t_window)
            try:
                await self._verify_and_commit_batch(batch, met, loop)
            except asyncio.CancelledError:
                raise
            except Exception:
                # One bad batch (device error, malformed-but-decodable
                # vote, transient executor failure) must not kill this
                # task: the node would keep enqueueing votes that no
                # one ever verifies — consensus halting while gossip
                # and RPC still look healthy. Degrade to per-vote HOST
                # verification — but still OFF the event loop and
                # outside _state_mtx (a device failure during a
                # 10k-sig burst must not turn into seconds of on-loop
                # crypto that blocks gossip, timeouts and RPC); the
                # mutex is then held only per-vote for the tally.
                self.logger.exception(
                    "vote batch of %d failed; degrading to host-verify "
                    "off-loop", len(batch))
                chain_id = self.state.chain_id

                def _host_verify_all(b=batch, cid=chain_id):
                    out = []
                    for vote, _pid, pk, _vals in b:
                        try:
                            out.append(pk.verify_signature(
                                vote.sign_bytes(cid), vote.signature))
                        except Exception:
                            out.append(False)
                    return out

                try:
                    verdicts = await loop.run_in_executor(
                        None, _host_verify_all)
                except Exception:
                    self.logger.exception(
                        "degraded host verify failed; dropping batch")
                    continue
                per_peer: dict[str, list[int]] = {}
                for (vote, peer_id, _, _), ok in zip(batch, verdicts):
                    if peer_id:
                        counts = per_peer.setdefault(peer_id, [0, 0])
                        counts[0 if ok else 1] += 1
                    if not ok:
                        self.logger.debug(
                            "degraded path rejected vote from %r",
                            peer_id)
                        continue
                    try:
                        async with self._state_mtx:
                            await self._try_add_vote(vote, peer_id,
                                                     preverified=True)
                    except Exception:
                        self.logger.exception(
                            "dropping unprocessable vote from %r", peer_id)
                # Same trust feedback as the happy path: a peer
                # streaming invalid votes must not farm free host
                # crypto just because the device is down. Guarded per
                # peer like the happy path — an exception escaping
                # this except-handler would kill the scheduler task,
                # the silent-halt mode this fallback exists to prevent.
                rep = self.reporter_fn()
                if rep is not None:
                    for peer_id, (good, bad) in per_peer.items():
                        try:
                            rep.observe(peer_id, good=good, bad=bad)
                            if bad:
                                await rep.enforce(
                                    peer_id, "invalid vote signature")
                        except Exception:
                            self.logger.exception(
                                "trust feedback failed for %r", peer_id)

    def _batch_verdicts(self, batch, chain_id):
        """Per-lane verdicts for a vote micro-batch (runs in the
        executor, off the event loop).

        Lanes group by the validator set each vote resolved against
        (current height vs last-commit precommits); each group goes
        through ValidatorSet.verify_live: for a set with resident comb
        tables ONE structured launch of vote_batch_max lanes with
        device-assembled sign bytes (VoteSignBatch: one template group
        per distinct (type, height, round, block_id)), whatever the
        batch's length from the device threshold up; for any other set
        the structured->bytes->host ladder every commit-verify call
        site uses. Its launches carry the ledger tag `votes`."""
        import numpy as _np

        from ..crypto.tpu import ledger as tpu_ledger
        from ..types.sign_batch import VoteSignBatch

        verdicts = _np.zeros(len(batch), bool)
        groups: dict[int, tuple] = {}
        for j, (vote, _peer, _pk, vals) in enumerate(batch):
            entry = groups.get(id(vals))
            if entry is None:
                groups[id(vals)] = entry = (vals, [])
            entry[1].append(j)
        for vals, idxs in groups.values():
            votes = [batch[j][0] for j in idxs]
            lanes = [v.validator_index for v in votes]
            sigs = [v.signature for v in votes]
            def picked(pick, votes=votes):
                return votes if pick is None else [votes[i] for i in pick]

            with tpu_ledger.workload("votes"):
                verdicts[_np.asarray(idxs)] = vals.verify_live(
                    lanes,
                    lambda pick: VoteSignBatch(chain_id, picked(pick)),
                    lambda pick: [v.sign_bytes(chain_id)
                                  for v in picked(pick)],
                    sigs, self.config.vote_batch_max)
        return verdicts

    async def _verify_and_commit_batch(self, batch, met, loop) -> None:
        met.vote_batch_size.observe(len(batch))
        chain_id = self.state.chain_id
        with tracing.TRACER.span(tracing.CONSENSUS_VOTE_BATCH,
                                 lanes=len(batch)):
            if len(batch) > 1:
                # Device (or host-oracle) verify OFF the event loop:
                # gossip, RPC and timeouts keep running during a
                # 10k-lane burst. TRACER.wrap carries the vote-batch
                # span into the executor thread so the crypto spans
                # recorded there keep their consensus lineage.
                verdicts = await loop.run_in_executor(
                    None, tracing.TRACER.wrap(self._batch_verdicts),
                    batch, chain_id)
            else:
                verdicts = self._batch_verdicts(batch, chain_id)
        per_peer: dict[str, list[int]] = {}  # peer -> [good, bad]
        added = rejected = 0
        tally = tracing.TRACER.begin(tracing.CONSENSUS_VOTE_TALLY,
                                     parent=self._ht_span,
                                     votes=len(batch))
        for (vote, peer_id, _, _), ok in zip(batch, verdicts):
            if peer_id:
                counts = per_peer.setdefault(peer_id, [0, 0])
                counts[0 if ok else 1] += 1
            if not ok:
                rejected += 1
                self.logger.debug(
                    "batch-verify rejected vote from %r (val %s)",
                    peer_id, vote.validator_address.hex(),
                )
                continue
            # Per-vote containment: once tallying has begun, one
            # vote's commit failure must not throw the WHOLE batch to
            # the degraded fallback — that would re-verify and
            # re-report trust for votes already processed here.
            try:
                async with self._state_mtx:
                    added += await self._try_add_vote(vote, peer_id,
                                                      preverified=True)
            except Exception:
                self.logger.exception(
                    "dropping unprocessable vote from %r", peer_id)
        tally.set_attr("added", added)
        tally.set_attr("rejected", rejected)
        tally.end()
        # Trust metric feedback on VERIFIED outcomes: credit good
        # lanes, debit rejected ones, disconnect on collapsed trust
        # (behaviour.py; a peer streaming well-formed-but-invalid
        # votes decays to a stop instead of farming reputation).
        rep = self.reporter_fn()
        if rep is not None:
            for peer_id, (good, bad) in per_peer.items():
                try:
                    rep.observe(peer_id, good=good, bad=bad)
                    if bad:
                        await rep.enforce(peer_id,
                                          "invalid vote signature")
                except Exception:
                    self.logger.exception(
                        "trust feedback failed for %r", peer_id)

    async def _try_add_vote(self, vote: Vote, peer_id: str,
                            preverified: bool = False) -> bool:
        """reference tryAddVote (state.go:1845): conflicting votes
        become evidence; late precommits for the last height extend
        rs.last_commit."""
        try:
            return await self._add_vote(vote, peer_id, preverified)
        except ConflictingVoteError as e:
            if self.priv_validator_address == vote.validator_address:
                self.logger.error(
                    "found conflicting vote from ourselves; height %d",
                    vote.height,
                )
                return False
            if self.evpool is not None and e.existing is not None:
                from ..state import median_time
                from ..types.evidence import DuplicateVoteEvidence

                # The evidence timestamp must equal the header time of
                # the block at the EVIDENCE height — peers' pools reject
                # any other timestamp (reference state.go:1868-76 uses
                # the LastCommit median; we additionally handle the
                # late-vote case, where the conflicting vote is for the
                # already-committed height and that block's time is
                # simply state.last_block_time).
                if vote.height == self.state.last_block_height or \
                        self.rs.last_commit is None:
                    ts = self.state.last_block_time
                    vals = self.rs.last_validators
                else:
                    ts = median_time(self.rs.last_commit.make_commit(),
                                     self.rs.last_validators)
                    vals = self.rs.validators
                ev = DuplicateVoteEvidence.from_votes(
                    e.existing, vote, ts, vals,
                )
                self.evpool.add_evidence_from_consensus(ev)
            return False
        except VoteSetError as e:
            self.logger.debug("vote rejected: %s", e)
            return False

    async def _add_vote(self, vote: Vote, peer_id: str,
                        preverified: bool = False) -> bool:
        rs = self.rs
        verify = not preverified
        # late precommit for the previous height (state.go:1901)
        if vote.height + 1 == rs.height and vote.type == VoteType.PRECOMMIT:
            if rs.step != RoundStep.NEW_HEIGHT or rs.last_commit is None:
                return False
            added = rs.last_commit.add_vote(vote, verify=verify)
            if added:
                if self.speculation is not None:
                    # the next block's LastCommit holds this precommit
                    # too: at 10,000 validators a third of a commit
                    # arrives after the +2/3, and a lane the plane
                    # never saw is a lane the LastCommit check verifies
                    # again, three times a height
                    self.speculation.observe_precommit(vote)
                self._publish_vote(vote)
                # reference addVote fires EventVote here too, and the
                # reactor tells every peer (broadcastHasVoteMessage):
                # peers still see this node at vote.height until its
                # next NewRoundStep
                self._broadcast_has_vote(vote)
            return added
        if vote.height != rs.height:
            return False

        added = rs.votes.add_vote(vote, peer_id, verify=verify)
        if not added:
            return False
        if self.speculation is not None and \
                vote.type == VoteType.PRECOMMIT:
            # patch the verify-ahead lane (conflicting/nil votes are
            # handled inside: they poison the lane, never serve)
            self.speculation.observe_precommit(vote)
        self._publish_vote(vote)
        self._broadcast_has_vote(vote)

        if vote.type == VoteType.PREVOTE:
            await self._on_prevote_added(vote)
        else:
            await self._on_precommit_added(vote)
        return True

    def _broadcast_has_vote(self, vote: Vote) -> None:
        traced = tracing.TRACER.enabled
        t0 = _time.perf_counter_ns() if traced else 0
        self._broadcast("has_vote", m.HasVoteMessage(
            vote.height, vote.round, int(vote.type), vote.validator_index
        ))
        if traced:
            tracing.TRACER.leaf(
                tracing.CONSENSUS_HAS_VOTE, t0,
                fold_key=(tracing.CONSENSUS_HAS_VOTE, id(self)),
                parent=self._ht_span)

    def _publish_vote(self, vote: Vote) -> None:
        if self.event_bus is not None:
            self.event_bus.publish_vote(EventDataVote(vote))
        self._broadcast("vote", vote)

    async def _on_prevote_added(self, vote: Vote) -> None:
        """reference addVote prevote handling (state.go:1950-2032)."""
        rs = self.rs
        prevotes = rs.votes.prevotes(vote.round)
        bid, has_maj = prevotes.two_thirds_majority()

        if has_maj and bid is not None and not bid.is_nil():
            # unlock if a later polka contradicts our lock (state.go:1965)
            if (rs.locked_block is not None
                    and rs.locked_round < vote.round <= rs.round
                    and rs.locked_block.hash() != bid.hash):
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                if self.event_bus is not None:  # state.go:1987
                    self.event_bus.publish_unlock(EventDataRoundState(
                        rs.height, rs.round, rs.step.name))
            # track valid block (state.go:1984)
            if rs.valid_round < vote.round <= rs.round:
                if rs.proposal_block is not None and rs.proposal_block.hash() == bid.hash:
                    rs.valid_round = vote.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
                    if self.event_bus is not None:  # state.go:2013
                        self.event_bus.publish_valid_block(
                            EventDataRoundState(rs.height, rs.round,
                                                rs.step.name))
                elif rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                    bid.part_set_header
                ):
                    # polka for a block we don't have: start fetching it
                    rs.proposal_block = None
                    rs.proposal_block_parts = PartSet(
                        bid.part_set_header.total, bid.part_set_header.hash
                    )

        if rs.round < vote.round and prevotes.has_two_thirds_any():
            await self._enter_new_round(rs.height, vote.round)
        elif rs.round == vote.round and rs.step >= RoundStep.PREVOTE:
            if has_maj and (rs.proposal_complete() or bid is None or bid.is_nil()):
                await self._enter_precommit(rs.height, vote.round)
            elif prevotes.has_two_thirds_any() and rs.step == RoundStep.PREVOTE:
                await self._enter_prevote_wait(rs.height, vote.round)
        elif (rs.proposal is not None
              and 0 <= rs.proposal.pol_round == vote.round
              and rs.step == RoundStep.PROPOSE
              and rs.proposal_complete()):
            await self._enter_prevote(rs.height, rs.round)

    async def _on_precommit_added(self, vote: Vote) -> None:
        """reference addVote precommit handling (state.go:2034-2067)."""
        rs = self.rs
        precommits = rs.votes.precommits(vote.round)
        bid, has_maj = precommits.two_thirds_majority()
        if has_maj:
            if bid is None or bid.is_nil():
                # +2/3 precommitted nil: straight to the next round
                await self._enter_new_round(rs.height, vote.round + 1)
            else:
                await self._enter_new_round(rs.height, vote.round)
                await self._enter_precommit(rs.height, vote.round)
                await self._enter_commit(rs.height, vote.round)
                if self.config.skip_timeout_commit and precommits.has_all():
                    await self._enter_new_round(self.rs.height, 0)
        elif rs.round <= vote.round and precommits.has_two_thirds_any():
            await self._enter_new_round(rs.height, vote.round)
            await self._enter_precommit_wait(rs.height, vote.round)

    async def _sign_add_vote(self, type_: VoteType, hash_: bytes,
                             part_set_header) -> Vote | None:
        """reference signAddVote (state.go:2139)."""
        if self.priv_validator is None or self.rs.validators is None:
            return None
        if not self.rs.validators.has_address(self.priv_validator_address):
            return None
        idx, _ = self.rs.validators.get_by_address(self.priv_validator_address)
        block_id = (
            BlockID(hash_, part_set_header) if hash_ else None
        )
        vote = Vote(
            type=type_,
            height=self.rs.height,
            round=self.rs.round,
            block_id=block_id,
            timestamp=self._vote_time(),
            validator_address=self.priv_validator_address,
            validator_index=idx,
        )
        try:
            res = self.priv_validator.sign_vote(self.state.chain_id, vote)
            if asyncio.iscoroutine(res):
                await res  # remote signer round-trip
        except Exception as e:
            self.logger.error("failed to sign vote: %r", e)
            return None
        self._send_internal(m.VoteMessage(vote))
        return vote

    def _vote_time(self) -> int:
        """now, but strictly after the block we're voting on
        (reference voteTime, state.go:2120)."""
        now = _clock.time_ns()
        time_iota = max(
            self.state.consensus_params.block.time_iota_ms, 1
        ) * 1_000_000
        min_time = 0
        if self.rs.locked_block is not None:
            min_time = self.rs.locked_block.header.time + time_iota
        elif self.rs.proposal_block is not None:
            min_time = self.rs.proposal_block.header.time + time_iota
        return max(now, min_time)

    # -- WAL catchup replay (reference consensus/replay.go:94) --

    async def _catchup_replay(self) -> None:
        assert self.wal is not None
        self.wal.repair()
        height = self.state.last_block_height
        msgs, found = self.wal.search_for_end_height(height)
        if not found and height > 0:
            return  # nothing in-flight
        self._replay_mode = True
        try:
            for tm in msgs:
                inner = tm.msg
                if isinstance(inner, EndHeightMessage):
                    break
                if isinstance(inner, MsgInfo):
                    try:
                        cmsg = m.decode_consensus_msg(inner.msg_bytes)
                    except ValueError:
                        continue
                    await self._handle_msg(_QueuedMsg(cmsg, inner.peer_id))
                elif isinstance(inner, TimeoutInfo):
                    # timeouts are re-derived live, not replayed
                    pass
        finally:
            self._replay_mode = False
        self.logger.info("replayed %d WAL messages for height %d",
                         len(msgs), self.rs.height)

    # -- public API (reactor / rpc) --

    def _funnel_class(self, msg) -> bool:
        """True = high class (round-critical: votes, proposals — the
        messages that move steps); False = low class (bulk data that
        is re-gossiped on demand and may be shed under flood)."""
        return isinstance(msg, (m.VoteMessage, m.ProposalMessage))

    def _shed_duplicate_vote(self, msg) -> bool:
        """Under funnel pressure, a vote already tallied is the first
        thing to shed: it would burn a funnel slot and a device lane
        to change nothing. Only consulted once the funnel is half
        full — the normal path stays probe-free."""
        if not isinstance(msg, m.VoteMessage) or \
                not self.peer_funnel.pressured():
            return False
        vs = self._target_vote_set(msg.vote)
        if vs is not None and vs.is_duplicate(msg.vote):
            # advisory: the drop is counted, but losing an ALREADY-
            # TALLIED duplicate is not information loss — it must not
            # flip the process-wide level to "shedding" during the
            # ordinary multi-peer gossip redundancy of a busy round
            CONTROLLER.shed("consensus.funnel.votes", advisory=True)
            return True
        return False

    async def add_peer_msg(self, msg, peer_id: str,
                           raw: bytes | None = None,
                           spent_ns: int = 0) -> None:
        """Priority-aware admission into the bounded funnel. High
        class blocks when full — backpressure onto the calling peer's
        recv loop, matching the reference's `cs.peerMsgQueue <-
        msgInfo` channel send (state.go:456; the 10k-validator scale
        test pinned that a burst must slow the sender, not raise).
        Low class (block parts / catchup) sheds when full instead:
        missing parts are re-requested by gossip, and a data flood
        must never wedge votes behind it. `raw`: the bytes `msg`
        was decoded from, which the WAL then records as they are;
        `spent_ns`: the caller's own time on it (its decode), which
        consensus.receive counts in, and sums beside as `decode_ms`
        (with `shaped`, the VoteMessages that were decoded by their
        shape and say so themselves)."""
        qm = _QueuedMsg(msg, peer_id, raw, spent_ns)
        if self._funnel_class(msg):
            if self._shed_duplicate_vote(msg):
                return
            await self.peer_funnel.put_high(qm)
        else:
            self.peer_funnel.put_low(qm)

    def add_peer_msg_nowait(self, msg, peer_id: str) -> None:
        """Non-blocking variant for sync call sites (test hooks);
        raises QueueFull for the high class instead of applying
        backpressure (the low class sheds, as in add_peer_msg)."""
        qm = _QueuedMsg(msg, peer_id)
        if self._funnel_class(msg):
            if self._shed_duplicate_vote(msg):
                return
            self.peer_funnel.put_high_nowait(qm)
        else:
            self.peer_funnel.put_low(qm)

    def get_round_state(self) -> RoundState:
        return self.rs

    async def wait_for_height(self, height: int, timeout: float = 60.0) -> None:
        deadline = _clock.monotonic() + timeout
        while self.rs.height <= height:
            remaining = deadline - _clock.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"height {height} not reached (at {self.rs.height})"
                )
            try:
                await asyncio.wait_for(
                    asyncio.shield(self._height_done.wait()), remaining
                )
            except asyncio.TimeoutError:
                raise TimeoutError(
                    f"height {height} not reached (at {self.rs.height})"
                )
