"""Evidence pool: pending/committed byzantine-behavior proof storage,
gossip feed, and proposal supply (reference: evidence/pool.go:29).

Pending evidence lives in the DB (prefix 0x00, keyed height‖hash so
iteration is proposal order) and on a CList the reactor's per-peer
broadcast routines walk. Committed hashes (prefix 0x01) block
re-admission forever; expiry prunes pending entries per the consensus
params' max-age (both height AND time must exceed, reference
pool.go:576 isExpired).

A block's evidence is checked as ONE batch (check_evidence): the
reference's checks in the reference's order and its error, but one
load of the validators and the block time a height and one signature
batch a validator set (evidence/verify.py); and marked committed in
ONE durable write (update)."""

from __future__ import annotations

import logging

from ..libs import tracing
from ..libs.clist import CList
from ..libs.tracing import TRACER
from ..types.evidence import Evidence, evidence_from_bytes
from .verify import (
    ChainLoads, EvidenceError, prepare, signature_errors, verify_evidence,
)

logger = logging.getLogger("evidence")

_PENDING = b"\x00"
_COMMITTED = b"\x01"


def _key(prefix: bytes, ev: Evidence, h: bytes | None = None) -> bytes:
    """`h`: ev.hash() where the caller holds it (an evidence is
    mutable and caches nothing: each hash() encodes it anew)."""
    return prefix + ev.height().to_bytes(8, "big") + (h or ev.hash())


class Pool:
    def __init__(self, db, state_store, block_store):
        self.db = db
        self.state_store = state_store
        self.block_store = block_store
        self.state = state_store.load()
        self.evidence_list = CList()  # gossip feed
        self._elements: dict[bytes, object] = {}  # hash -> its CElement
        self._pending_bytes = 0
        # refill the gossip list from persisted pending evidence
        for _, v in self.db.iterate_prefix(_PENDING):
            ev = evidence_from_bytes(v)
            self._elements[ev.hash()] = self.evidence_list.push_back(ev)
            self._pending_bytes += len(v)
        self._set_pool_gauges()

    def _set_pool_gauges(self) -> None:
        from ..libs.metrics import evidence_metrics

        met = evidence_metrics()
        met.pool_size.set(len(self.evidence_list))
        met.pool_bytes.set(self._pending_bytes)

    # -- queries --

    def pending_evidence(self, max_bytes: int) -> list[Evidence]:
        """Ordered by height for proposal inclusion
        (reference: PendingEvidence)."""
        out, total = [], 0
        for _, v in self.db.iterate_prefix(_PENDING):
            if max_bytes >= 0 and total + len(v) > max_bytes:
                break
            out.append(evidence_from_bytes(v))
            total += len(v)
        return out

    def is_committed(self, ev: Evidence, h: bytes | None = None) -> bool:
        """`h`: ev.hash(), where the caller holds it."""
        return self.db.get(_key(_COMMITTED, ev, h)) is not None

    def is_pending(self, ev: Evidence, h: bytes | None = None) -> bool:
        """Pending evidence is on the gossip list and in the db alike
        (_persist_pending, _drop_pending): the list's index answers,
        with no read."""
        return (h or ev.hash()) in self._elements

    # -- ingestion --

    def add_evidence(self, ev: Evidence) -> None:
        """From a peer or RPC: fully verified before admission
        (reference: pool.go:120 AddEvidence)."""
        h = ev.hash()
        if self.is_pending(ev, h) or self.is_committed(ev, h):
            return
        ev.validate_basic()
        verify_evidence(ev, self.state, self.state_store, self.block_store)
        from ..libs.metrics import evidence_metrics

        evidence_metrics().verified.inc()
        self._persist_pending(ev)
        logger.info("added evidence %s h=%d", type(ev).__name__, ev.height())

    def add_evidence_from_consensus(self, ev: Evidence) -> None:
        """Consensus observed the equivocation itself — no re-verify
        (reference: pool.go AddEvidenceFromConsensus)."""
        h = ev.hash()
        if self.is_pending(ev, h) or self.is_committed(ev, h):
            return
        self._persist_pending(ev)
        logger.info("added own-observed evidence %s h=%d",
                    type(ev).__name__, ev.height())

    def _persist_pending(self, ev: Evidence) -> None:
        raw = ev.to_bytes()
        self.db.set(_key(_PENDING, ev), raw)
        self._pending_bytes += len(raw)
        self._elements[ev.hash()] = self.evidence_list.push_back(ev)
        self._set_pool_gauges()

    # -- block validation hook --

    def check_evidence(self, evlist: list[Evidence]) -> None:
        """Every piece proposed in a block must be valid and fresh
        (reference: pool.go:181 CheckEvidence). Raises what the
        reference's loop raises: the error of the first evidence, in
        list order, that fails any check — a signature check included
        — although the signatures are verified last, all at once."""
        seen = set()
        fresh: list[tuple[int, Evidence]] = []  # neither pending nor known
        failed = None   # (list index, error) of the first failing check
        for k, ev in enumerate(evlist):
            h = ev.hash()
            if h in seen:
                failed = k, EvidenceError("duplicate evidence in block")
                break
            seen.add(h)
            if self.is_committed(ev, h):
                failed = k, EvidenceError("evidence was already committed")
                break
            if not self.is_pending(ev, h):
                fresh.append((k, ev))
        if fresh:
            with TRACER.span(tracing.EVIDENCE_CHECK,
                             evidence=len(fresh)) as span:
                failed = self._verify_fresh(fresh, span) or failed
        if failed is not None:
            # which evidence, beside why (the reference's
            # ErrInvalidEvidence carries the evidence itself)
            failed[1].evidence_index = failed[0]
            raise failed[1]

    def _verify_fresh(self, fresh, span):
        """(list index, error) of the first of `fresh` that fails, or
        None. The checks before the signatures run in list order and
        stop at the first failure; the signature lanes of what came
        before it are then verified per validator set (sets with one
        membership_digest share a batch), and a bad signature there
        is the earlier failure."""
        failed = None
        loads = ChainLoads(self.state_store, self.block_store)
        # membership digest -> (set, [(list index, evidence, validator)])
        groups: dict[bytes, tuple] = {}
        with TRACER.span(tracing.EVIDENCE_COLLECT):
            for k, ev in fresh:
                try:
                    ev.validate_basic()
                    lanes = prepare(ev, self.state, loads)
                except Exception as e:
                    failed = k, e
                    break
                if lanes is not None:
                    vals, index = lanes
                    groups.setdefault(vals.membership_digest(),
                                      (vals, []))[1].append((k, ev, index))
        span.set_attr("heights", len(loads._times))
        span.set_attr("sets", len(groups))
        span.set_attr("lanes", 2 * sum(len(g) for _, g in groups.values()))
        for vals, group in groups.values():
            errors = signature_errors(self.state.chain_id, vals,
                                      [(ev, i) for _, ev, i in group])
            for (k, _, _), err in zip(group, errors):
                if err is not None and (failed is None or k < failed[0]):
                    failed = k, err
        return failed

    # -- post-commit --

    def update(self, state, committed: list[Evidence]) -> None:
        """Mark committed, drop from pending, prune expired
        (reference: pool.go Update). A block's evidence is one
        write_batch: marked and dropped together, durably, or not at
        all."""
        self.state = state
        from ..libs.metrics import evidence_metrics

        evidence_metrics().committed.inc(len(committed))
        if committed:
            with TRACER.span(tracing.EVIDENCE_UPDATE,
                             committed=len(committed)):
                ops = []
                for ev in committed:
                    h = ev.hash()
                    ops.append((_key(_COMMITTED, ev, h), b"\x01"))
                    ops += self._drop_pending(ev, h)
                self.db.write_batch(ops)
        self._prune_expired()
        self._set_pool_gauges()

    def _drop_pending(self, ev: Evidence,
                      h: bytes) -> list[tuple[bytes, None]]:
        """Take `ev` (hash `h`) off the gossip list and the pending
        byte count; returns the delete of its pending row for the
        caller's batch. Pending evidence is on the list and in the db
        alike (_persist_pending), so one not on the list costs no
        read and returns nothing."""
        e = self._elements.pop(h, None)
        if e is None:
            return []
        self.evidence_list.remove(e)
        k = _key(_PENDING, ev, h)
        self._pending_bytes -= len(self.db.get(k) or b"")
        return [(k, None)]

    def _prune_expired(self) -> None:
        p = self.state.consensus_params.evidence
        for k, v in list(self.db.iterate_prefix(_PENDING)):
            ev = evidence_from_bytes(v)
            age_blocks = self.state.last_block_height - ev.height()
            ev_time = getattr(ev, "timestamp", 0)
            age_ns = self.state.last_block_time - ev_time
            if age_blocks > p.max_age_num_blocks and \
                    age_ns > p.max_age_duration_ns:
                self.db.write_batch(self._drop_pending(ev, ev.hash()))
                logger.info("pruned expired evidence h=%d", ev.height())

    def size(self) -> int:
        return len(self.evidence_list)
