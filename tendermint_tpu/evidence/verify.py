"""Evidence verification (reference: evidence/verify.go).

The reference verifies one evidence and one signature at a time
(VerifyDuplicateVote's two checks after one another, verify.go:165-225;
CheckEvidence's loop over a block's list, pool.go:181). Here the work
is cut in two: `prepare` is every check of Verify BEFORE a signature
(the block time and the validators of the evidence's height, expiry,
and for a DuplicateVoteEvidence all of VerifyDuplicateVote but its two
signatures), and `signature_errors` verifies the two lanes of any
number of prepared DuplicateVoteEvidence in ONE call of their
validator set's `_batch_verify_lanes` (ledger tag `evidence`): ed25519
lanes of a large set ride its resident tables, sr25519 lanes one
launch of their kernel, a couple of lanes stay on the host as
BatchVerifier decides. `verify_evidence` / `verify_duplicate_vote`
(gossip, RPC, `Pool.add_evidence`) are the one-evidence case;
`Pool.check_evidence` hands in a block's whole list. The errors, and
which evidence raises first, are those of the one-at-a-time loop."""

from __future__ import annotations

from ..types.evidence import DuplicateVoteEvidence, Evidence


class EvidenceError(Exception):
    pass


class ChainLoads:
    """The committed chain as evidence verification reads it: a
    height's block time and validator set, each loaded once however
    many pieces of evidence name the height."""

    def __init__(self, state_store, block_store):
        self.state_store = state_store
        self.block_store = block_store
        self._times: dict[int, int] = {}
        self._vals: dict[int, object] = {}

    def block_time(self, height: int) -> int:
        when = self._times.get(height)
        if when is None:
            meta = self.block_store.load_block_meta(height)
            if meta is None:
                raise EvidenceError(
                    f"no committed block at evidence height {height}")
            when = self._times[height] = meta.header.time
        return when

    def validators(self, height: int):
        vals = self._vals.get(height)
        if vals is None:
            vals = self.state_store.load_validators(height)
            if vals is None:
                raise EvidenceError(
                    f"no validator set at height {height}")
            self._vals[height] = vals
        return vals


def prepare(ev: Evidence, state, loads: ChainLoads):
    """Every check of Verify that comes before a signature
    (reference: evidence/verify.go:25 Verify + prepare checks). A
    LightClientAttackEvidence is verified whole, here. For a
    DuplicateVoteEvidence returns (validator set of its height, the
    validator's index in it): its two signatures are still to verify
    (signature_errors); None for any other evidence."""
    height = ev.height()
    header_time = loads.block_time(height)

    # expiry relative to consensus params (reference verify.go:33-47:
    # expired only when BOTH height- and time-age are exceeded)
    p = state.consensus_params.evidence
    age_blocks = state.last_block_height - height
    age_ns = state.last_block_time - header_time
    if age_blocks > p.max_age_num_blocks and age_ns > p.max_age_duration_ns:
        raise EvidenceError(
            f"evidence from height {height} is too old "
            f"({age_blocks} blocks / {age_ns / 1e9:.0f}s)")

    if isinstance(ev, DuplicateVoteEvidence):
        vals = loads.validators(height)
        return vals, check_duplicate_vote(ev, vals, header_time)
    from ..light.types import LightClientAttackEvidence

    if isinstance(ev, LightClientAttackEvidence):
        verify_light_client_attack(
            ev, state.chain_id, loads.validators(height), header_time,
            loads.state_store, loads.block_store)
        return None
    raise EvidenceError(f"unknown evidence type {type(ev).__name__}")


def verify_evidence(ev: Evidence, state, state_store, block_store) -> None:
    """Full verification of one evidence against committed chain
    state."""
    lanes = prepare(ev, state, ChainLoads(state_store, block_store))
    if lanes is not None:
        vals, index = lanes
        _raise_first(signature_errors(state.chain_id, vals, [(ev, index)]))


def check_duplicate_vote(ev: DuplicateVoteEvidence, vals,
                         header_time: int) -> int:
    """VerifyDuplicateVote (reference: evidence/verify.go:165) up to
    its signatures; returns the validator's index in `vals`."""
    a, b = ev.vote_a, ev.vote_b

    if a.height != b.height or a.round != b.round or a.type != b.type:
        raise EvidenceError("votes are from different H/R/S")
    if a.validator_address != b.validator_address:
        raise EvidenceError("votes are from different validators")
    if a.block_id == b.block_id:
        raise EvidenceError("votes are for the same block id")
    from ..types.vote_set import _block_key
    if not _block_key(a.block_id) < _block_key(b.block_id):
        raise EvidenceError("votes not in canonical order")

    index, val = vals.get_by_address(a.validator_address)
    if val is None:
        raise EvidenceError(
            f"validator {a.validator_address.hex()} not in set at "
            f"height {a.height}")

    # recorded powers must match the valset (they feed ABCI punishment)
    if ev.validator_power != val.voting_power:
        raise EvidenceError(
            f"validator power mismatch: {ev.validator_power} != "
            f"{val.voting_power}")
    if ev.total_voting_power != vals.total_voting_power():
        raise EvidenceError("total voting power mismatch")
    if ev.timestamp != header_time:
        raise EvidenceError(
            f"evidence time {ev.timestamp} != block time {header_time}")
    return index


def signature_errors(chain_id: str, vals,
                     items: list[tuple[DuplicateVoteEvidence, int]]
                     ) -> list[EvidenceError | None]:
    """The two signatures of each (evidence, its validator's index in
    `vals`), all in one batch through the set's verify ladder; per
    evidence the error VerifyDuplicateVote would raise (vote A is
    looked at before vote B), or None."""
    from ..crypto.tpu import ledger as tpu_ledger
    from ..types.sign_batch import VoteSignBatch

    votes = [v for ev, _ in items for v in (ev.vote_a, ev.vote_b)]
    lanes = [i for _, i in items for _ in (0, 1)]
    sigs = [v.signature for v in votes]

    def picked(pick):
        return votes if pick is None else [votes[i] for i in pick]

    msgs = vals.structured_or_bytes(
        lanes,
        lambda pick: VoteSignBatch(chain_id, picked(pick)),
        lambda pick: [v.sign_bytes(chain_id) for v in picked(pick)])
    with tpu_ledger.workload("evidence"):
        ok, verdicts = vals._batch_verify_lanes(lanes, msgs, sigs)
    if ok:
        return [None] * len(items)
    return [None if verdicts[2 * k] and verdicts[2 * k + 1]
            else EvidenceError("invalid signature on vote "
                               + ("B" if verdicts[2 * k] else "A"))
            for k in range(len(items))]


def _raise_first(errors) -> None:
    for err in errors:
        if err is not None:
            raise err


def verify_duplicate_vote(ev: DuplicateVoteEvidence, chain_id: str,
                          vals, header_time: int) -> None:
    """reference: evidence/verify.go:165 VerifyDuplicateVote."""
    index = check_duplicate_vote(ev, vals, header_time)
    _raise_first(signature_errors(chain_id, vals, [(ev, index)]))


def verify_light_client_attack(ev, chain_id: str, common_vals,
                               common_time: int, state_store,
                               block_store) -> None:
    """reference: evidence/verify.go:123 VerifyLightClientAttack.

    The commit of the conflicting block must verify against OUR chain:
    through the common-height valset with 1/3 trust when the fork is
    non-adjacent (a lunatic attack forges later valsets, so only the
    common ancestor's set is meaningful), or through the valset at that
    exact height for a same-height equivocation. The recorded byzantine
    set, powers and timestamp are re-derived and must match — they feed
    ABCI punishment and must not be attacker-chosen.
    """
    from ..light.types import (
        SignedHeader, compute_byzantine_validators,
        conflicting_header_is_invalid,
    )
    from ..types.validator_set import VerificationError

    cb = ev.conflicting_block
    sh = cb.signed_header
    c_height = sh.header.height

    # Our signed header at the conflicting height — the evidence must
    # actually conflict with the committed chain, and its commit round
    # feeds the equivocation/amnesia classification below. ONLY the
    # canonical commit (stored with block c_height+1) may be used: a
    # locally-seen commit can be at a DIFFERENT round than the
    # canonical one, which would make the equivocation-vs-amnesia
    # classification — and thus accept/reject — node-dependent.
    # Tip evidence simply fails here and is retried by gossip once the
    # next block lands (reference getSignedHeader does the same).
    trusted_meta = block_store.load_block_meta(c_height)
    trusted_commit = block_store.load_block_commit(c_height)
    if trusted_meta is None or trusted_commit is None:
        raise EvidenceError(
            f"no committed header+commit at conflicting height "
            f"{c_height} (commit lands with block {c_height + 1})")
    if trusted_meta.header.hash() == sh.header.hash():
        raise EvidenceError("conflicting block matches the committed chain")
    trusted_sh = SignedHeader(trusted_meta.header, trusted_commit)

    # The conflicting block must be self-consistent (its commit signs
    # its header; its valset matches the header's validators_hash).
    try:
        cb.validate_basic(chain_id)
    except ValueError as e:
        raise EvidenceError(f"invalid conflicting block: {e}") from e

    try:
        if ev.common_height != c_height:
            # Non-adjacent fork: >= 1/3 of the common valset must have
            # signed the conflicting block (reference verify.go:138).
            common_vals.verify_commit_light_trusting(
                chain_id, sh.commit, 1, 3)
        else:
            # Same-height evidence must be a correctly-derived header
            # (equivocation/amnesia); a lunatic header at the SAME
            # height is nonsense — lunatic forks require an earlier
            # common height (reference verify.go:135-139).
            if conflicting_header_is_invalid(sh.header,
                                             trusted_meta.header):
                raise EvidenceError(
                    "common height equals conflicting height, so the "
                    "conflicting block must be correctly derived, but "
                    "its deterministic header fields differ")
            vals_at = state_store.load_validators(c_height)
            if vals_at is None:
                raise EvidenceError(
                    f"no validator set at height {c_height}")
            if sh.header.validators_hash != vals_at.hash():
                raise EvidenceError(
                    "equivocation evidence with foreign validator set")
            vals_at.verify_commit_light(
                chain_id, sh.commit.block_id, c_height, sh.commit)
    except VerificationError as e:
        raise EvidenceError(
            f"conflicting commit failed verification: {e}") from e

    expected = compute_byzantine_validators(common_vals, trusted_sh, cb)
    got = ev.byzantine_validators
    # Mismatch is attacker-chosen punishment data; an empty set that
    # MATCHES the derivation is legitimate amnesia evidence (reference
    # verify.go accepts a nil byzantine set for amnesia attacks).
    if [(v.address, v.voting_power) for v in got] != \
            [(v.address, v.voting_power) for v in expected]:
        raise EvidenceError("byzantine validator set mismatch")
    if ev.total_voting_power != common_vals.total_voting_power():
        raise EvidenceError("total voting power mismatch")
    if ev.timestamp != common_time:
        raise EvidenceError(
            f"evidence time {ev.timestamp} != common block time "
            f"{common_time}")
