"""A validator set that holds ed25519 and sr25519 keys side by side:
the verify sites split its lanes by key type (types/validator_set.py
_batch_verify_lanes), the ed25519 lanes ride the tables of the set's
ed25519 keys, the sr25519 lanes one launch of their kernel, and the
verdicts come back in the caller's lane order.

Pinned here, against the sequential host loop the reference runs
(`PubKey.verify_signature`, one signature at a time): verify_commit,
verify_commit_light, verify_commit_light_trusting and the fast-sync
window accept and refuse alike and name the same index, with a fault
planted in each key type; the launch ledger shows the split; and a set
that is all ed25519 leaves exactly the records, the digest and the one
launch it left before there was a split.

24 ed25519 + 12 sr25519 validators with _EXPAND_MIN at 4: the shapes
(24 keys of tables, 128-lane buckets) the structured-verify tests
compile anyway.
"""

import hashlib

import numpy as np
import pytest

import tendermint_tpu.types.validator_set as vs_mod
from tendermint_tpu.blockchain import verify_ahead
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519_ref as edr
from tendermint_tpu.crypto import sr25519_ref as srr
from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
from tendermint_tpu.crypto.sr25519 import Sr25519PubKey
from tendermint_tpu.crypto.tpu import expanded as ex
from tendermint_tpu.crypto.tpu import ledger
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER
from tendermint_tpu.types.block import (
    BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (
    SplitSignBytes, ValidatorSet, VerificationError)

CHAIN = "mixed-chain"
BASE_TS = 1_753_928_000_000_000_000
N_ED, N_SR = 24, 12


def _bid(tag: int) -> BlockID:
    return BlockID(bytes([tag]) * 32, PartSetHeader(3, bytes([tag + 1]) * 32))


class Chain:
    """The set and, by address, each validator's signer."""

    def __init__(self, n_ed: int, n_sr: int, tag: bytes):
        self.sign = {}
        vals = []
        total = n_ed + n_sr
        for i in range(total):
            seed = hashlib.sha256(tag + b"%d" % i).digest()
            # the sr25519 keys spread evenly among the others
            if (i + 1) * n_sr // total > i * n_sr // total:
                pk = Sr25519PubKey(srr.public_key_from_mini(seed))
                self.sign[pk.address()] = \
                    lambda m, s=seed: srr.sign(s, m)
            else:
                pk = Ed25519PubKey(edr.public_key_from_seed(seed))
                self.sign[pk.address()] = \
                    lambda m, s=seed: edr.sign(s, m)
            vals.append(Validator.new(pk, 10))
        self.vals = ValidatorSet(vals)
        self.kinds = [v.pub_key.type_name for v in self.vals.validators]

    def first(self, kind: str) -> int:
        return self.kinds.index(kind)

    def commit(self, height: int, bad=()) -> Commit:
        sigs = [CommitSig(BlockIDFlag.COMMIT, v.address, BASE_TS + i, b"")
                for i, v in enumerate(self.vals.validators)]
        commit = Commit(height, 0, _bid(height), sigs)
        for i, v in enumerate(self.vals.validators):
            sig = self.sign[v.address](commit.vote_sign_bytes(CHAIN, i))
            if i in bad:   # one bit of s: a well-formed wrong signature
                sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
            sigs[i].signature = sig
        return commit


@pytest.fixture(scope="module")
def mixed():
    chain = Chain(N_ED, N_SR, b"mixed-set")
    assert chain.kinds.count("sr25519") == N_SR
    # both key types among the first third (what a trusting check reads)
    assert {"ed25519", "sr25519"} <= set(chain.kinds[:10])
    return chain


@pytest.fixture(autouse=True)
def small_sets_take_the_tables(monkeypatch):
    cbatch.reset_breakers()
    monkeypatch.setattr(vs_mod, "_EXPAND_MIN", 4)


# ------------------------------------------------ the sequential oracle

def host_bad(vals, commit, slots) -> list[int]:
    """The reference's loop: slots whose signature its own key's
    verify_signature refuses, one at a time, on the host."""
    return [s for s in slots
            if not vals.validators[s].pub_key.verify_signature(
                commit.vote_sign_bytes(CHAIN, s),
                commit.signatures[s].signature)]


def light_slots(vals, commit, num=2, den=3) -> list[int]:
    need, tally, out = vals.total_voting_power() * num, 0, []
    for s, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        out.append(s)
        tally += vals.validators[s].voting_power
        if tally * den > need:
            break
    return out


def expected(vals, commit, slots):
    bad = host_bad(vals, commit, slots)
    return f"invalid signature(s) at index(es) {bad}" if bad else None


def outcome(fn):
    try:
        fn()
    except VerificationError as e:
        return str(e)
    return None


FAULTS = {"none": (), "ed25519": ("ed25519",), "sr25519": ("sr25519",),
          "both": ("ed25519", "sr25519")}


def _bad(chain, fault):
    return [chain.first(kind) for kind in FAULTS[fault]]


@pytest.mark.parametrize("fault", list(FAULTS))
def test_verify_commit_matches_the_host_loop(mixed, fault):
    vals, commit = mixed.vals, mixed.commit(5, _bad(mixed, fault))
    got = outcome(lambda: vals.verify_commit(CHAIN, _bid(5), 5, commit))
    assert got == expected(vals, commit, range(len(vals)))
    assert (got is None) == (fault == "none")


@pytest.mark.parametrize("fault", list(FAULTS))
def test_verify_commit_light_matches_the_host_loop(mixed, fault):
    vals, commit = mixed.vals, mixed.commit(6, _bad(mixed, fault))
    got = outcome(lambda: vals.verify_commit_light(
        CHAIN, _bid(6), 6, commit))
    assert got == expected(vals, commit, light_slots(vals, commit))
    assert (got is None) == (fault == "none")


@pytest.mark.parametrize("fault", list(FAULTS))
def test_verify_commit_light_trusting_matches_the_host_loop(mixed, fault):
    vals, commit = mixed.vals, mixed.commit(7, _bad(mixed, fault))
    got = outcome(lambda: vals.verify_commit_light_trusting(
        CHAIN, commit, 1, 3))
    assert got == expected(vals, commit, light_slots(vals, commit, 1, 3))
    assert (got is None) == (fault == "none")


@pytest.mark.parametrize("fault", list(FAULTS))
def test_window_refuses_the_block_the_host_loop_refuses(mixed, fault):
    vals = mixed.vals
    commits = [mixed.commit(h, _bad(mixed, fault) if h == 12 else ())
               for h in (11, 12, 13)]
    items = [(_bid(c.height), c.height, c) for c in commits]
    results = verify_ahead._batch_verify_window(vals, CHAIN, items)
    want = [bool(host_bad(vals, c, light_slots(vals, c)))
            for c in commits]
    assert [r is not None for r in results] == want
    assert want == [False, fault != "none", False]
    if fault != "none":
        assert "height 12" in str(results[1])


def test_plan_execute_names_the_commit_slot(mixed):
    """CommitVerifyPlan.raise_invalid maps a verdict to its slot
    whichever key type the lane holds."""
    bad = [mixed.first("sr25519")]
    commit = mixed.commit(8, bad)
    plan = mixed.vals.plan_commit_light(CHAIN, _bid(8), 8, commit)
    assert isinstance(plan.msgs, SplitSignBytes)
    with pytest.raises(VerificationError, match=rf"\[{bad[0]}\]"):
        plan.execute()
    # the cross-plan form: full bytes of every lane, in lane order
    assert [m for _, m, _ in plan.triples()] == \
        [commit.vote_sign_bytes(CHAIN, s) for s in plan.slots]


# ------------------------------------------------------- where lanes go

def _new_records(fn):
    before = ledger.evicted() + len(ledger.snapshot())
    fn()
    snap = ledger.snapshot()
    return snap[len(snap) - (ledger.evicted() + len(snap) - before):]


def test_ledger_shows_the_split(mixed):
    vals, commit = mixed.vals, mixed.commit(9)
    TRACER.clear()
    recs = _new_records(
        lambda: vals.verify_commit(CHAIN, _bid(9), 9, commit))
    assert sorted((r["kernel"], r["lanes"]) for r in recs) == \
        [("sr25519", N_SR), ("structured", N_ED)]
    assert all(r["verdict"] == "ok" for r in recs)
    spans = {r[0]: r[6] for r in TRACER.snapshot()}
    assert spans[tracing.VERIFY_LANE_SPLIT] == {
        "ed25519": N_ED, "sr25519": N_SR, "other": 0}
    assert spans[tracing.CRYPTO_SR_MERLIN]["lanes"] == N_SR
    assert spans[tracing.VERIFY_TABLES]["keys"] == N_ED
    assert tracing.CRYPTO_HOST_VERIFY not in spans


def test_tables_are_built_over_the_ed25519_keys(mixed):
    vals = mixed.vals
    vals.verify_commit(CHAIN, _bid(10), 10, mixed.commit(10))
    cols = vals._columns()
    ed_keys = [v.pub_key.bytes() for v in vals.validators
               if v.pub_key.type_name == "ed25519"]
    assert cols.ed_keys == ed_keys and not cols.all_ed25519
    assert cols.digest == ex.key_digest(ed_keys)
    assert list(ex._CACHE[cols.digest].pubkeys) == ed_keys
    # a validator's row in the tables: its rank among the ed25519 keys
    rows = [r for r in cols.ed_row.tolist() if r >= 0]
    assert rows == list(range(N_ED))
    assert [r < 0 for r in cols.ed_row.tolist()] == \
        [k != "ed25519" for k in mixed.kinds]


def test_use_expanded_asks_the_ed25519_part(mixed, monkeypatch):
    vals = mixed.vals
    sr = [i for i, k in enumerate(mixed.kinds) if k == "sr25519"]
    ed = [i for i, k in enumerate(mixed.kinds) if k == "ed25519"]
    assert vals._use_expanded(range(len(vals)))
    assert vals._use_expanded(ed[:4] + sr)
    assert not vals._use_expanded(ed[:3] + sr)   # three ed25519 lanes
    assert not vals._use_expanded(sr)
    # the cap is on the keys the tables hold, not on the set
    monkeypatch.setattr(ex, "max_keys", lambda: N_ED)
    assert vals._use_expanded(range(len(vals)))
    monkeypatch.setattr(ex, "max_keys", lambda: N_ED - 1)
    assert not vals._use_expanded(range(len(vals)))


def test_warm_device_tables_warms_the_ed25519_part(mixed, monkeypatch):
    warmed = []
    monkeypatch.setattr(ex, "warm_async",
                        lambda keys: warmed.append(list(keys)) or "thread")
    assert mixed.vals.warm_device_tables() == "thread"
    assert warmed == [mixed.vals._columns().ed_keys]
    only_sr = Chain(0, 6, b"only-sr").vals
    assert only_sr.warm_device_tables() is None


def test_lanes_in_any_order_come_back_in_that_order(mixed):
    """_batch_verify_lanes handed full bytes in a scrambled lane order
    (the speculation plane's miss batch): verdict i is lane i's."""
    vals = mixed.vals
    bad_ed, bad_sr = mixed.first("ed25519"), mixed.first("sr25519")
    commit = mixed.commit(14, [bad_ed, bad_sr])
    lanes = list(np.random.default_rng(14).permutation(len(vals)))
    msgs = [commit.vote_sign_bytes(CHAIN, s) for s in lanes]
    sigs = [commit.signatures[s].signature for s in lanes]
    ok, verdicts = vals._batch_verify_lanes(lanes, msgs, sigs)
    assert not ok
    assert [lanes[i] for i in np.flatnonzero(~verdicts)] == \
        [s for s in lanes if s in (bad_ed, bad_sr)]


def test_picked_cuts_each_commit_to_its_share():
    per_commit = [("a", np.array([0, 2, 4]), None),
                  ("b", np.array([1, 3]), None),
                  ("c", np.array([5, 6, 7]), None)]
    assert verify_ahead._picked(per_commit, None) is per_commit
    got = verify_ahead._picked(per_commit, np.array([1, 2, 5, 7]))
    assert [(c, s.tolist()) for c, s, _ in got] == \
        [("a", [2, 4]), ("c", [5, 7])]


# ------------------------------------------- a set that is all ed25519

def test_all_ed25519_set_leaves_what_it_left_before():
    """Launch for launch: one structured launch over every lane, the
    digest of all the keys in set order, no partition, and the span
    sequence of a commit check as it was."""
    chain = Chain(N_ED, 0, b"all-ed")
    vals, commit = chain.vals, chain.commit(15)
    vals.verify_commit(CHAIN, _bid(15), 15, commit)   # builds the tables
    TRACER.clear()
    recs = _new_records(
        lambda: vals.verify_commit(CHAIN, _bid(15), 15, commit))
    assert [(r["kernel"], r["lanes"], r["workload"]) for r in recs] == \
        [("structured", N_ED, "consensus")]
    cols = vals._columns()
    keys = [v.pub_key.bytes() for v in vals.validators]
    assert cols.all_ed25519 and cols.ed_keys is cols.pubkeys
    assert cols.pubkeys == keys and cols.digest == ex.key_digest(keys)
    assert cols.ed_row is None and cols.kind_code is None
    assert [r[0] for r in TRACER.snapshot()] == [
        "verify.collect", "verify.sign_batch", "verify.tables",
        "crypto.pack", "crypto.dispatch", "crypto.device_exec",
        "crypto.readback", "crypto.verify", "verify.commit"]
    msgs = vals._commit_msgs(CHAIN, commit, np.arange(N_ED),
                             list(range(N_ED)))
    assert not isinstance(msgs, SplitSignBytes)


def test_membership_digest_tells_key_types_apart():
    a = Chain(4, 2, b"md").vals
    b = Chain(4, 2, b"md").vals
    assert a.membership_digest() == b.membership_digest()
    assert a.copy().membership_digest() == a.membership_digest()
    assert a.copy()._columns().ed_keys == a._columns().ed_keys
    assert Chain(6, 0, b"md").vals.membership_digest() != \
        a.membership_digest()


def test_columns_are_held_only_once_whole():
    """The window's thread and the apply loop ask one set for its
    columns side by side: whoever finds them held must find the lane
    split's arrays in them (PR 35: a warm-up thread met a mixed set's
    columns with `kind_code` still None)."""
    seen = []

    class Watched(ValidatorSet):
        def __setattr__(self, name, value):
            if name == "_cols" and value is not None:
                seen.append((value.all_ed25519, value.kind_code is None,
                             value.ed_row is None,
                             len(value.ed_keys) == len(value.pubkeys)))
            super().__setattr__(name, value)

    vals = Watched(list(Chain(4, 2, b"whole").vals.validators))
    vals._columns()
    assert seen == [(False, False, False, False)]
