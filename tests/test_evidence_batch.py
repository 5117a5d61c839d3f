"""A block's evidence is ONE batch (evidence/__init__.py
Pool.check_evidence over evidence/verify.py prepare +
signature_errors), and what it raises is what the reference's
one-at-a-time loop raises: the error of the first evidence, in list
order, that fails any check, a signature check included.

The oracle is that loop, kept HERE to the reference's letter
(evidence/pool.go CheckEvidence, verify.go Verify and
VerifyDuplicateVote): one evidence after another, vote A's signature
and then vote B's, each by its own key's verify_signature on the host.
The lists run over three heights and both key types of a set of
6 ed25519 + 3 sr25519 validators.
"""

import dataclasses
import hashlib
from types import SimpleNamespace

import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey
from tendermint_tpu.crypto.tpu import ledger
from tendermint_tpu.evidence import Pool
from tendermint_tpu.evidence.verify import EvidenceError
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB, SqliteDB
from tendermint_tpu.libs.metrics import evidence_metrics
from tendermint_tpu.libs.tracing import TRACER
from tendermint_tpu.state import make_genesis_state
from tendermint_tpu.state.store import Store
from tendermint_tpu.types.block import BlockID, PartSetHeader
from tendermint_tpu.types.evidence import DuplicateVoteEvidence
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote, VoteType
from tendermint_tpu.types.vote_set import _block_key

CHAIN = "evidence-batch"
T0 = 1_753_928_000_000_000_000
HEIGHTS = (1, 2, 3)


def _bid(tag: int) -> BlockID:
    return BlockID(bytes([tag]) * 32, PartSetHeader(1, bytes([tag]) * 32))


def _key(i: int):
    seed = hashlib.sha256(b"evb%d" % i).digest()
    return (Sr25519PrivKey if i % 3 == 2 else Ed25519PrivKey)(seed)


class FakeBlockStore:
    """Block metas of heights 1..3, counted."""

    def __init__(self):
        self.loads = []

    def load_block_meta(self, height):
        self.loads.append(height)
        if height not in HEIGHTS:
            return None
        return SimpleNamespace(header=SimpleNamespace(time=T0 + height))


class Ctx:
    def __init__(self, db=None):
        self.keys = [_key(i) for i in range(9)]
        gdoc = GenesisDoc(
            chain_id=CHAIN, genesis_time=T0,
            validators=[GenesisValidator(k.pub_key(), 10 + i)
                        for i, k in enumerate(self.keys)])
        gdoc.validate_and_complete()
        state = make_genesis_state(gdoc)
        self.vals = state.validators
        self.by_addr = {k.pub_key().address(): k for k in self.keys}
        self.state_store = Store(MemDB())
        for h in HEIGHTS:
            self.state_store.save_validator_set(h, self.vals)
        state.last_block_height = 3
        state.last_block_time = T0 + 3
        self.state_store.save(state)
        self.block_store = FakeBlockStore()
        self.pool = Pool(db or MemDB(), self.state_store, self.block_store)
        self.val_loads = []
        real = self.state_store.load_validators

        def load_validators(height):
            self.val_loads.append(height)
            return real(height)

        self.state_store.load_validators = load_validators

    def vote(self, key, height, bid, vtype=VoteType.PRECOMMIT, round_=0):
        idx, val = self.vals.get_by_address(key.pub_key().address())
        v = Vote(type=vtype, height=height, round=round_, block_id=bid,
                 timestamp=T0 + height, validator_address=val.address,
                 validator_index=idx)
        v.signature = key.sign(v.sign_bytes(CHAIN))
        return v

    def evidence(self, i: int, height: int, vtype=VoteType.PRECOMMIT):
        key = self.keys[i]
        return DuplicateVoteEvidence.from_votes(
            self.vote(key, height, _bid(10 + height), vtype),
            self.vote(key, height, _bid(20 + height), vtype),
            T0 + height, self.vals)

    def valid_list(self):
        """Nine pieces: every validator once, three heights, both key
        types at each height, prevotes and precommits."""
        return [self.evidence(i, HEIGHTS[i % 3],
                              VoteType.PREVOTE if i % 2 else
                              VoteType.PRECOMMIT)
                for i in range(9)]


# ------------------------------------------------------------ the oracle

def ref_verify_duplicate_vote(ev, chain_id, vals, header_time):
    a, b = ev.vote_a, ev.vote_b
    if a.height != b.height or a.round != b.round or a.type != b.type:
        raise EvidenceError("votes are from different H/R/S")
    if a.validator_address != b.validator_address:
        raise EvidenceError("votes are from different validators")
    if a.block_id == b.block_id:
        raise EvidenceError("votes are for the same block id")
    if not _block_key(a.block_id) < _block_key(b.block_id):
        raise EvidenceError("votes not in canonical order")
    _, val = vals.get_by_address(a.validator_address)
    if val is None:
        raise EvidenceError(
            f"validator {a.validator_address.hex()} not in set at "
            f"height {a.height}")
    if ev.validator_power != val.voting_power:
        raise EvidenceError(
            f"validator power mismatch: {ev.validator_power} != "
            f"{val.voting_power}")
    if ev.total_voting_power != vals.total_voting_power():
        raise EvidenceError("total voting power mismatch")
    if ev.timestamp != header_time:
        raise EvidenceError(
            f"evidence time {ev.timestamp} != block time {header_time}")
    if not val.pub_key.verify_signature(a.sign_bytes(chain_id),
                                        a.signature):
        raise EvidenceError("invalid signature on vote A")
    if not val.pub_key.verify_signature(b.sign_bytes(chain_id),
                                        b.signature):
        raise EvidenceError("invalid signature on vote B")


def ref_check_evidence(ctx, evlist):
    pool, state = ctx.pool, ctx.pool.state
    seen = set()
    for ev in evlist:
        h = ev.hash()
        if h in seen:
            raise EvidenceError("duplicate evidence in block")
        seen.add(h)
        if pool.is_committed(ev):
            raise EvidenceError("evidence was already committed")
        if pool.is_pending(ev):
            continue
        ev.validate_basic()
        height = ev.height()
        meta = FakeBlockStore().load_block_meta(height)
        if meta is None:
            raise EvidenceError(
                f"no committed block at evidence height {height}")
        p = state.consensus_params.evidence
        age_blocks = state.last_block_height - height
        age_ns = state.last_block_time - meta.header.time
        if age_blocks > p.max_age_num_blocks and \
                age_ns > p.max_age_duration_ns:
            raise EvidenceError(
                f"evidence from height {height} is too old "
                f"({age_blocks} blocks / {age_ns / 1e9:.0f}s)")
        ref_verify_duplicate_vote(ev, state.chain_id, ctx.vals,
                                  meta.header.time)


def outcome(fn):
    try:
        fn()
    except Exception as e:   # the type and the message are the contract
        return type(e), str(e)
    return None


# ------------------------------------------------------------ the faults
# each takes (ctx, list) and spoils the evidence at index `at`

def _flip(sig: bytes) -> bytes:
    return sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]


def bad_sig_a(ctx, evs, at):
    evs[at].vote_a.signature = _flip(evs[at].vote_a.signature)


def bad_sig_b(ctx, evs, at):
    evs[at].vote_b.signature = _flip(evs[at].vote_b.signature)


def wrong_power(ctx, evs, at):
    evs[at].validator_power += 1


def wrong_total_power(ctx, evs, at):
    evs[at].total_voting_power += 1


def wrong_time(ctx, evs, at):
    evs[at].timestamp += 1


def validator_absent(ctx, evs, at):
    outsider = Ed25519PrivKey(hashlib.sha256(b"outsider").digest())
    ev = evs[at]
    for v in (ev.vote_a, ev.vote_b):
        v.validator_address = outsider.pub_key().address()
        v.signature = outsider.sign(v.sign_bytes(CHAIN))


def same_block_id(ctx, evs, at):
    evs[at].vote_b = dataclasses.replace(evs[at].vote_a)


def wrong_order(ctx, evs, at):
    evs[at].vote_a, evs[at].vote_b = evs[at].vote_b, evs[at].vote_a


def different_round(ctx, evs, at):
    ev = evs[at]
    ev.vote_b.round = 1
    ev.vote_b.signature = ctx.by_addr[ev.vote_b.validator_address].sign(
        ev.vote_b.sign_bytes(CHAIN))


def duplicate_in_list(ctx, evs, at):
    evs[at] = evs[0]


def already_committed(ctx, evs, at):
    ctx.pool.update(ctx.pool.state, [evs[at]])


def expired(ctx, evs, at):
    """Height 1 falls out of a one-block, one-nanosecond window (the
    state stands at height 3); heights 2 and 3 stay in."""
    params = ctx.pool.state.consensus_params
    ctx.pool.state.consensus_params = dataclasses.replace(
        params, evidence=dataclasses.replace(
            params.evidence, max_age_num_blocks=1, max_age_duration_ns=1))
    assert evs[at].height() == 1


def no_block_at_height(ctx, evs, at):
    ev = evs[at]
    for v in (ev.vote_a, ev.vote_b):
        v.height = 9
        v.signature = ctx.by_addr[v.validator_address].sign(
            v.sign_bytes(CHAIN))


def bad_vote_shape(ctx, evs, at):
    evs[at].vote_a.signature = b""   # validate_basic's ValueError


ONE_FAULT = [
    (bad_sig_a, 4), (bad_sig_a, 5),      # an ed25519 and an sr25519 lane
    (bad_sig_b, 1), (bad_sig_b, 8),
    (wrong_power, 4), (wrong_total_power, 2), (wrong_time, 7),
    (validator_absent, 3), (same_block_id, 6), (wrong_order, 5),
    (different_round, 2), (duplicate_in_list, 4), (already_committed, 5),
    (expired, 3), (no_block_at_height, 6), (bad_vote_shape, 2),
]
# two faults in one list: the first in list order is the one raised,
# whichever of them is a signature
TWO_FAULTS = [
    ((bad_sig_b, 2), (wrong_power, 6)),
    ((wrong_power, 2), (bad_sig_a, 6)),
    ((bad_sig_a, 7), (duplicate_in_list, 3)),
    ((already_committed, 6), (bad_sig_b, 5)),
    ((bad_sig_a, 8), (bad_sig_b, 1)),       # sr25519 lane, ed25519 lane
    ((wrong_time, 4), (validator_absent, 1)),
]


def _ids(cases):
    def name(c):
        return f"{c[0].__name__}@{c[1]}"
    return [name(c) if callable(c[0]) else "+".join(map(name, c))
            for c in cases]


@pytest.fixture(autouse=True)
def closed_breakers():
    cbatch.reset_breakers()


def _run_both(faults):
    ctx = Ctx()
    evs = ctx.valid_list()
    for fault, at in faults:
        fault(ctx, evs, at)
    want = outcome(lambda: ref_check_evidence(ctx, evs))
    got = outcome(lambda: ctx.pool.check_evidence(evs))
    assert got == want
    return want


def test_valid_list_passes_both():
    assert _run_both([]) is None


@pytest.mark.parametrize("fault,at", ONE_FAULT, ids=_ids(ONE_FAULT))
def test_one_fault_raises_what_the_loop_raises(fault, at):
    assert _run_both([(fault, at)]) is not None


@pytest.mark.parametrize("faults", TWO_FAULTS, ids=_ids(TWO_FAULTS))
def test_first_fault_in_list_order_wins(faults):
    want = _run_both(faults)
    first = min(faults, key=lambda f: f[1])
    alone = _run_both([first])
    assert want == alone


def test_pending_evidence_is_skipped_not_verified():
    ctx = Ctx()
    evs = ctx.valid_list()
    bad_sig_a(ctx, evs, 4)
    ctx.pool.add_evidence_from_consensus(evs[4])   # this node saw it
    assert outcome(lambda: ref_check_evidence(ctx, evs)) is None
    ctx.pool.check_evidence(evs)


# ------------------------------------------------------ what it costs

def test_one_load_a_height_one_batch_a_set(monkeypatch):
    ctx = Ctx()
    evs = ctx.valid_list()
    calls = []
    real = ValidatorSet._batch_verify_lanes

    def spy(self, lanes, msgs, sigs, rows=None):
        if rows is None:   # a verify site's call, not the split's own
            calls.append((ledger.current_workload(), len(lanes)))
        return real(self, lanes, msgs, sigs, rows)

    monkeypatch.setattr(ValidatorSet, "_batch_verify_lanes", spy)
    TRACER.clear()
    before = len(ledger.snapshot()) + ledger.evicted()
    ctx.pool.check_evidence(evs)
    # three heights, three loads of each kind; the three sets loaded
    # hold one membership: ONE batch of 18 lanes
    assert sorted(ctx.val_loads) == list(HEIGHTS)
    assert sorted(ctx.block_store.loads) == list(HEIGHTS)
    assert calls == [("evidence", 18)]
    spans = {r[0]: r[6] for r in TRACER.snapshot()}
    assert spans[tracing.EVIDENCE_CHECK] == {
        "evidence": 9, "heights": 3, "sets": 1, "lanes": 18}
    assert tracing.EVIDENCE_COLLECT in spans
    # the six sr25519 lanes: one launch, tagged; the ed25519 lanes of
    # so small a batch stay on the host, as BatchVerifier decides
    snap = ledger.snapshot()
    new = snap[len(snap) - (len(snap) + ledger.evicted() - before):]
    assert [(r["workload"], r["kernel"], r["lanes"]) for r in new] == \
        [("evidence", "sr25519", 6)]


def test_nothing_to_verify_opens_no_span():
    ctx = Ctx()
    evs = ctx.valid_list()[:3]
    for ev in evs:
        ctx.pool.add_evidence_from_consensus(ev)
    TRACER.clear()
    ctx.pool.check_evidence(evs)
    assert not [r for r in TRACER.snapshot()
                if r[0].startswith("evidence.")]
    assert ctx.val_loads == [] and ctx.block_store.loads == []


def _unsigned_evidence(ctx, n):
    """Evidence nobody verifies here: update() takes a block's word."""
    out = []
    for j in range(n):
        key = ctx.keys[j % 9]
        va = ctx.vote(key, 1 + j % 3, _bid(30))
        vb = ctx.vote(key, 1 + j % 3, _bid(31))
        va.timestamp = vb.timestamp = T0 + 1000 + j   # 200 distinct
        out.append(DuplicateVoteEvidence.from_votes(
            va, vb, T0 + 1 + j % 3, ctx.vals))
    return out


def test_update_of_200_is_one_durable_commit(tmp_path):
    db = SqliteDB(str(tmp_path / "evidence.sqlite"), synchronous="FULL")
    ctx = Ctx(db)
    evs = _unsigned_evidence(ctx, 200)
    assert len({ev.hash() for ev in evs}) == 200
    for ev in evs[:120]:            # 120 this node holds as pending
        ctx.pool.add_evidence_from_consensus(ev)
    assert ctx.pool.size() == 120
    met = evidence_metrics()
    assert met.pool_size.value() == 120
    walked = []
    front = ctx.pool.evidence_list.front

    def counted_front():
        walked.append(1)
        return front()

    ctx.pool.evidence_list.front = counted_front
    TRACER.clear()
    committed = evs[:100] + evs[120:]    # 100 pending + 80 never seen
    ctx.pool.update(ctx.pool.state, committed)
    writes = [r for r in TRACER.snapshot() if r[0] == tracing.DB_WRITE]
    assert sum(r[6].get("n", 1) for r in writes) == 1
    assert writes[0][6]["ops"] == 180 + 100   # marks + pending deletes
    (upd,) = [r for r in TRACER.snapshot()
              if r[0] == tracing.EVIDENCE_UPDATE]
    assert upd[6] == {"committed": 180}
    assert not walked                # no walk of the list per evidence
    assert all(ctx.pool.is_committed(ev) for ev in committed)
    assert not any(ctx.pool.is_committed(ev) for ev in evs[100:120])
    assert not any(ctx.pool.is_pending(ev) for ev in committed)
    left = evs[100:120]
    assert [ev.hash() for ev in ctx.pool.evidence_list] == \
        [ev.hash() for ev in left]
    assert ctx.pool.size() == 20 == met.pool_size.value()
    assert met.pool_bytes.value() == sum(len(ev.to_bytes()) for ev in left)
    assert [ev.hash() for ev in ctx.pool.pending_evidence(-1)] == \
        sorted((ev.hash() for ev in left),
               key=lambda h: next(ev.height().to_bytes(8, "big") + h
                                  for ev in left if ev.hash() == h))
    # proposed again, committed evidence is refused; reopened, the
    # marks and the pending rows are what was written
    with pytest.raises(EvidenceError, match="already committed"):
        ctx.pool.check_evidence([committed[0]])
    db.close()
    again = Pool(SqliteDB(str(tmp_path / "evidence.sqlite")),
                 ctx.state_store, ctx.block_store)
    assert again.size() == 20
    assert all(again.is_committed(ev) for ev in committed)


def test_one_evidence_is_the_one_evidence_case():
    """verify_evidence / verify_duplicate_vote (gossip, RPC,
    add_evidence) go through the same two functions."""
    from tendermint_tpu.evidence.verify import (
        verify_duplicate_vote, verify_evidence)

    ctx = Ctx()
    for i in (4, 5):     # an ed25519 and an sr25519 validator
        ev = ctx.evidence(i, 2)
        verify_evidence(ev, ctx.pool.state, ctx.state_store,
                        ctx.block_store)
        verify_duplicate_vote(ev, CHAIN, ctx.vals, T0 + 2)
        bad_sig_b(ctx, [ev], 0)
        with pytest.raises(EvidenceError, match="vote B"):
            verify_duplicate_vote(ev, CHAIN, ctx.vals, T0 + 2)
        ctx.pool.add_evidence(ctx.evidence(i, 3))
    assert ctx.pool.size() == 2
