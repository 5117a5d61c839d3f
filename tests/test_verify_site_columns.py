"""The verify sites read a Commit into columns (types/sign_batch.py
CommitColumns) and a validator set into columns (ValidatorSet._columns).

Three things are pinned here:

* differential: verify_commit, plan_commit_light and the fast-sync
  window builder against the per-slot loops they replaced, kept HERE as
  the oracle, over seeded commits with every odd slot the loops had an
  answer for — equal lanes, signatures, tally, exception type and
  message, and a structured batch byte-equal to the old builder's;
* the per-set columns follow the set through every way the repo changes
  one, and hold the table cache's key, never the tables;
* nothing read from a Commit is remembered: a Commit changed in place
  is read again.
"""

import hashlib
import random
from types import SimpleNamespace

import numpy as np
import pytest

import tendermint_tpu.types.validator_set as vs_mod
from tendermint_tpu.blockchain import verify_ahead
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
from tendermint_tpu.crypto.tpu import expanded as ex
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER
from tendermint_tpu.types import canonical
from tendermint_tpu.types import sign_batch as sbm
from tendermint_tpu.types.block import (
    BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (
    MAX_TOTAL_VOTING_POWER, ValidatorSet, VerificationError)
from tendermint_tpu.types.vote import VoteType

CHAIN = "columns-chain"
HEIGHT = 77
BID = BlockID(b"\xa1" * 32, PartSetHeader(3, b"\xb2" * 32))
BASE_TS = 1_753_928_000_000_000_000
N = 24


# ------------------------------------------------------------ the oracle
# Yesterday's per-slot code, copied: what the columns must agree with.

def ref_vlen(v):
    bits = np.zeros(v.shape, np.int64)
    x = v.astype(np.int64)
    for shift in (32, 16, 8, 4, 2, 1):
        hi = x >= (1 << shift)
        bits += np.where(hi, shift, 0)
        x = np.where(hi, x >> shift, x)
    return (bits // 7 + 1).astype(np.int64)


def ref_varint_digits(out, col, v, ln):
    for j in range(ln):
        b = (v >> (7 * j)) & 0x7F
        if j < ln - 1:
            b = b | 0x80
        out[:, col + j] = b
    return col + ln


def ref_build_patches(pre_len, suf_len, group, ts):
    n = ts.shape[0]
    secs = ts // 1_000_000_000
    nanos = ts % 1_000_000_000
    ls = np.where(secs > 0, ref_vlen(np.maximum(secs, 1)), 0)
    ln = np.where(nanos > 0, ref_vlen(np.maximum(nanos, 1)), 0)
    pay = np.where(secs > 0, 1 + ls, 0) + np.where(nanos > 0, 1 + ln, 0)
    tsf_total = np.where(ts > 0, 2 + pay, 0)
    body = (pre_len[group].astype(np.int64) + tsf_total
            + suf_len[group])
    if body.size and body.max() >= 1 << 14:
        raise ValueError("sign bytes too long for structured batch")
    outer_len = np.where(body >= 128, 2, 1)
    patch = np.zeros((n, sbm.PATCH_W), np.uint8)
    split = outer_len.astype(np.int32)
    patch_len = (outer_len + tsf_total).astype(np.int32)
    key = (group.astype(np.int64) * 4 + (secs > 0) * 2
           + (nanos > 0)) * 1024 + ls * 64 + ln * 8 + outer_len
    for kv in np.unique(key):
        m = key == kv
        ol = int(outer_len[m][0])
        bd = int(body[m][0])
        if ol == 1:
            patch[m, 0] = bd
        else:
            patch[m, 0] = (bd & 0x7F) | 0x80
            patch[m, 1] = bd >> 7
        if int(tsf_total[m][0]) == 0:
            continue
        sub = np.zeros((int(m.sum()), sbm.PATCH_W - ol), np.uint8)
        sub[:, 0] = 0x2A
        sub[:, 1] = pay[m]
        col = 2
        if int((secs > 0)[m][0]):
            sub[:, col] = 0x08
            col = ref_varint_digits(sub, col + 1, secs[m], int(ls[m][0]))
        if int((nanos > 0)[m][0]):
            sub[:, col] = 0x10
            col = ref_varint_digits(sub, col + 1, nanos[m], int(ln[m][0]))
        patch[m, ol:] = sub
    return patch, split, patch_len


def ref_sign_batch(chain_id, commit, slots):
    """The old CommitSignBatch.__post_init__: the structured fields, or
    ValueError where it raised one."""
    n = len(slots)
    parts, group_of = [], {}
    group = np.zeros(n, np.int32)
    ts = np.zeros(n, np.int64)
    for i, slot in enumerate(slots):
        cs = commit.signatures[slot]
        if not 0 <= cs.timestamp < 1 << 63:
            raise ValueError("timestamp out of int64 range")
        ts[i] = cs.timestamp
        fb = cs.for_block()
        g = group_of.get(fb)
        if g is None:
            g = len(parts)
            group_of[fb] = g
            parts.append(canonical.vote_sign_parts(
                chain_id, int(VoteType.PRECOMMIT), commit.height,
                commit.round, cs.block_id_for(commit.block_id)))
        group[i] = g
    pre, pre_len, suf, suf_len = sbm._pack_templates(parts)
    patch, split, patch_len = ref_build_patches(pre_len, suf_len, group, ts)
    return SimpleNamespace(
        pre=pre, pre_len=pre_len, suf=suf, suf_len=suf_len, group=group,
        patch=patch, split=split, patch_len=patch_len)


def ref_msgs(chain_id, commit, slots):
    """What _commit_msgs handed the launch: the structured fields, or
    the full bytes where the old builder raised ValueError."""
    if not slots:
        return []
    try:
        return ref_sign_batch(chain_id, commit, slots)
    except ValueError:
        return [commit.vote_sign_bytes(chain_id, s) for s in slots]


def ref_verify_commit(vals, block_id, height, commit, total, launched):
    lanes, sigs, tallied = [], [], 0
    vals._check_commit_basics(block_id, height, commit)
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        val = vals.validators[idx]
        if cs.validator_address and cs.validator_address != val.address:
            raise VerificationError(
                f"wrong validator address in slot {idx}")
        lanes.append(idx)
        sigs.append(cs.signature)
        if cs.for_block():
            tallied += val.voting_power
    # the launch (every lane accepted), then the tally's verdict
    launched.append((lanes, sigs, ref_msgs(CHAIN, commit, lanes)))
    if 3 * tallied <= 2 * total():
        raise VerificationError(
            f"insufficient voting power: {tallied} of {total()}")


def ref_light_loop(vals, commit, need):
    lanes, sigs, tallied = [], [], 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        lanes.append(idx)
        sigs.append(cs.signature)
        tallied += vals.validators[idx].voting_power
        if 3 * tallied > need:
            break
    return lanes, sigs, tallied


def ref_plan_light(vals, block_id, height, commit, total):
    need = 2 * total()
    vals._check_commit_basics(block_id, height, commit)
    lanes, sigs, tallied = ref_light_loop(vals, commit, need)
    if 3 * tallied <= need:
        raise VerificationError(
            f"insufficient voting power: {tallied} of {total()}")
    return lanes, sigs, ref_msgs(CHAIN, commit, lanes)


def ref_window(vals, items, total):
    """(per-block results, lanes, sigs, per-commit slots) of the old
    fast-sync window builder, before any signature work."""
    results = [None] * len(items)
    lanes_all, sigs_all, per_commit = [], [], []
    for i, (bid, height, commit) in enumerate(items):
        try:
            vals._check_commit_basics(bid, height, commit)
            need = 2 * total()
            lanes, sigs, tallied = ref_light_loop(vals, commit, need)
            if 3 * tallied <= need:
                raise VerificationError(
                    f"insufficient voting power at height {height}")
        except Exception as e:
            results[i] = e
            continue
        lanes_all += lanes
        sigs_all += sigs
        per_commit.append((commit, lanes))
    return results, lanes_all, sigs_all, per_commit


# ------------------------------------------------------------- the cases

def _keys(n, tag=b"col"):
    return [Ed25519PubKey(hashlib.sha256(tag + b"%d" % i).digest())
            for i in range(n)]


def _valset(powers, tag=b"col"):
    return ValidatorSet([Validator.new(k, p)
                         for k, p in zip(_keys(len(powers), tag), powers)])


def _commit(vals, rng, absent=0.15, nil=0.1, height=HEIGHT, bid=BID):
    sigs = []
    for i, v in enumerate(vals.validators):
        r = rng.random()
        if r < absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BlockIDFlag.NIL if r < absent + nil else BlockIDFlag.COMMIT
        sigs.append(CommitSig(
            flag, bytes(bytearray(v.address)),  # equal, not identical
            BASE_TS + rng.randrange(10**10),
            bytes([rng.randrange(256)]) * 64))
    return Commit(height, 0, bid, sigs)


def _present(commit, rng, k=1, for_block=False):
    """k distinct present slots, ascending (for-block ones on request)."""
    idx = [i for i, cs in enumerate(commit.signatures)
           if (cs.for_block() if for_block else not cs.is_absent())]
    return sorted(rng.sample(idx, k))


def _other(addr):
    return bytes(b ^ 0xFF for b in addr)


def case_plain(vals, commit, rng):
    pass


def case_all_present(vals, commit, rng):
    for i, v in enumerate(vals.validators):
        commit.signatures[i] = CommitSig(
            BlockIDFlag.COMMIT, v.address, BASE_TS + i, b"\x07" * 64)


def case_all_absent(vals, commit, rng):
    commit.signatures[:] = [CommitSig.absent() for _ in vals.validators]


def case_mostly_nil(vals, commit, rng):
    for cs in commit.signatures[2:]:
        if not cs.is_absent():
            cs.block_id_flag = BlockIDFlag.NIL


def case_nil_first(vals, commit, rng):
    cs = commit.signatures[0]
    commit.signatures[0] = CommitSig(
        BlockIDFlag.NIL, vals.validators[0].address,
        cs.timestamp or BASE_TS, b"\x01" * 64)


def case_empty_address(vals, commit, rng):
    for i in _present(commit, rng, 2):
        commit.signatures[i].validator_address = b""


def case_wrong_address_twice(vals, commit, rng):
    for i in _present(commit, rng, 2):
        cs = commit.signatures[i]
        cs.validator_address = _other(cs.validator_address)


def case_short_address(vals, commit, rng):
    (i,) = _present(commit, rng)
    cs = commit.signatures[i]
    cs.validator_address = cs.validator_address[:19]


def case_address_on_absent_slot(vals, commit, rng):
    commit.signatures[5] = CommitSig(
        BlockIDFlag.ABSENT, _other(vals.validators[5].address), 0, b"")


def _set_ts(values):
    def case(vals, commit, rng):
        for i, t in zip(_present(commit, rng, len(values), for_block=True),
                        values):
            commit.signatures[i].timestamp = t
    return case


def case_ts_past_int64_on_absent_slot(vals, commit, rng):
    commit.signatures[3] = CommitSig(BlockIDFlag.ABSENT, b"", 1 << 63, b"")


def case_sig_lengths(vals, commit, rng):
    a, b = _present(commit, rng, 2)
    commit.signatures[a].signature = b"\x05" * 63
    commit.signatures[b].signature = b"\x06" * 65


def case_unknown_flags(vals, commit, rng):
    a, b, c = _present(commit, rng, 3)
    commit.signatures[a].block_id_flag = 7
    commit.signatures[b].block_id_flag = 300      # past a byte
    commit.signatures[c].block_id_flag = 1 << 70  # past int64


def case_one_second_apart_widths(vals, commit, rng):
    # seconds of two varint widths and a two-byte outer varint's edge
    a, b, c = _present(commit, rng, 3, for_block=True)
    commit.signatures[a].timestamp = 127 * 10**9 + 5
    commit.signatures[b].timestamp = 128 * 10**9
    commit.signatures[c].timestamp = 999_999_999


CASES = {
    "plain": case_plain,
    "all_present": case_all_present,
    "all_absent": case_all_absent,
    "mostly_nil": case_mostly_nil,
    "nil_first": case_nil_first,
    "empty_address": case_empty_address,
    "wrong_address_twice": case_wrong_address_twice,
    "short_address": case_short_address,
    "address_on_absent_slot": case_address_on_absent_slot,
    "ts_0_and_1ns": _set_ts([0, 1]),
    "ts_int64_max": _set_ts([(1 << 63) - 1]),
    "ts_2_63": _set_ts([1 << 63]),
    "ts_minus_1": _set_ts([-1]),
    "ts_past_int64_on_absent_slot": case_ts_past_int64_on_absent_slot,
    "sig_lengths": case_sig_lengths,
    "unknown_flags": case_unknown_flags,
    "seconds_of_two_widths": case_one_second_apart_widths,
}

# name -> powers of the N validators, from a seeded rng
POWERS = {
    "mixed": lambda rng: [rng.randrange(1, 1000) for _ in range(N)],
    "at_cap": lambda rng: [MAX_TOTAL_VOTING_POWER - (N - 1)] + [1] * (N - 1),
}


def _every_set_takes_the_expanded_path(monkeypatch):
    """As a 10,000-validator set does on the chip."""
    cbatch.reset_breakers()
    monkeypatch.setattr(vs_mod, "_EXPAND_MIN", 1)
    monkeypatch.setattr(ex, "max_keys", lambda: 1 << 20)


@pytest.fixture
def structured(monkeypatch):
    """Every set takes the expanded path's sign-bytes decision, and
    the launch is caught: what reaches it is what is compared."""
    _every_set_takes_the_expanded_path(monkeypatch)
    caught = []

    def launch(self, lanes, msgs, sigs):
        caught.append((lanes, sigs, msgs))
        return True, np.ones(len(lanes), bool)

    monkeypatch.setattr(ValidatorSet, "_batch_verify_lanes", launch)
    return caught


def _outcome(fn):
    try:
        return ("ok", fn())
    except Exception as e:  # the type and the message are the contract
        return (type(e), str(e))


STRUCT_FIELDS = ("pre", "pre_len", "suf", "suf_len", "group", "patch",
                 "split", "patch_len")


def _same_msgs(got, want):
    if isinstance(want, list):
        assert isinstance(got, list) and got == want
        return
    assert isinstance(got, sbm.StructuredSignBytes)
    for f in STRUCT_FIELDS:
        g, w = getattr(got, f), getattr(want, f)
        assert g.dtype == w.dtype and g.shape == w.shape, f
        assert g.tobytes() == w.tobytes(), f


def _same_launch(got, want):
    (lanes, sigs, msgs), (rlanes, rsigs, rmsgs) = got, want
    assert type(lanes) is list and lanes == rlanes
    assert all(type(i) is int for i in lanes)
    assert sigs == rsigs
    _same_msgs(msgs, rmsgs)


def _build(case, powers, seed):
    rng = random.Random(f"{case}/{powers}/{seed}")
    vals = _valset(POWERS[powers](rng))
    commit = _commit(vals, rng)
    CASES[case](vals, commit, rng)
    return vals, commit, rng


def _totals(vals):
    """The set's own total, and one that makes every tally fall short:
    then the refusal's message shows the tally."""
    real = vals.total_voting_power
    return real, lambda: 4 * real()


PARAMS = [(c, "mixed", s) for c in CASES for s in (1, 2)] + \
    [(c, "at_cap", 1) for c in ("plain", "mostly_nil", "all_present")]


@pytest.mark.parametrize("case,powers,seed", PARAMS)
def test_verify_commit_equals_the_per_slot_loop(structured, monkeypatch,
                                                case, powers, seed):
    vals, commit, _ = _build(case, powers, seed)
    for total in _totals(vals):
        monkeypatch.setattr(vals, "total_voting_power", total)
        del structured[:]
        launched = []
        want = _outcome(lambda: ref_verify_commit(
            vals, BID, HEIGHT, commit, total, launched))
        got = _outcome(lambda: vals.verify_commit(
            CHAIN, BID, HEIGHT, commit))
        assert got == want
        assert len(structured) == len(launched)
        for g, w in zip(structured, launched):
            _same_launch(g, w)


@pytest.mark.parametrize("case,powers,seed", PARAMS)
def test_plan_commit_light_equals_the_per_slot_loop(structured, monkeypatch,
                                                    case, powers, seed):
    vals, commit, _ = _build(case, powers, seed)
    for total in _totals(vals):
        monkeypatch.setattr(vals, "total_voting_power", total)
        want = _outcome(lambda: ref_plan_light(
            vals, BID, HEIGHT, commit, total))
        got = _outcome(lambda: vals.plan_commit_light(
            CHAIN, BID, HEIGHT, commit))
        if want[0] != "ok":
            assert got == want
            continue
        assert got[0] == "ok"
        plan = got[1]
        assert plan.slots == plan.lanes and plan.form == "light"
        _same_launch((plan.lanes, plan.sigs, plan.msgs), want[1])
        # the tally the selection stopped at, and no lane beyond it
        need = 2 * total()
        _, _, tallied = vals.light_selection(
            sbm.CommitColumns(commit), need)
        assert tallied == ref_light_loop(vals, commit, need)[2]
    assert structured == []  # planning verifies nothing


@pytest.mark.parametrize("case,powers,seed", PARAMS)
def test_window_builder_equals_the_per_slot_loop(structured, case, powers,
                                                 seed):
    """The window: this case's commit between a plain one, one of
    another height (refused by the basics) and one short of power."""
    vals, commit, rng = _build(case, powers, seed)
    bid2 = BlockID(b"\xc3" * 32, PartSetHeader(1, b"\xd4" * 32))
    plain = _commit(vals, rng, height=HEIGHT + 1, bid=bid2)
    short = _commit(vals, rng, absent=0.7, height=HEIGHT + 2, bid=bid2)
    items = [(bid2, HEIGHT + 1, plain), (BID, HEIGHT, commit),
             (BID, HEIGHT + 3, commit), (bid2, HEIGHT + 2, short)]
    want_results, lanes, sigs, per_commit = ref_window(
        vals, items, vals.total_voting_power)
    got_results = verify_ahead._batch_verify_window(vals, CHAIN, items)
    assert [(type(r), str(r)) for r in got_results] == \
        [(type(r), str(r)) for r in want_results]
    if not lanes:
        assert structured == []
        return
    assert len(structured) == 1
    try:
        want_msgs = sbm.MergedSignBatch(
            [ref_sign_batch(CHAIN, c, s) for c, s in per_commit])
    except ValueError:
        want_msgs = [c.vote_sign_bytes(CHAIN, s)
                     for c, slots in per_commit for s in slots]
    _same_launch(structured[0], (lanes, sigs, want_msgs))


def test_a_set_over_the_cap_still_raises_and_does_not_wrap(structured):
    """Such a set exists only by assignment (the constructor refuses
    it): its tallies are exact Python ints, and the refusal is
    total_voting_power()'s own."""
    rng = random.Random(5)
    vals = _valset([1] * N)
    vals.validators = [Validator.new(k, (1 << 62) + i)
                       for i, k in enumerate(_keys(N, b"big"))]
    vals._total = None
    commit = _commit(vals, rng, absent=0.0, nil=0.0)
    cols = sbm.CommitColumns(commit)
    _, _, tallied = vals.light_selection(cols, 1 << 80)
    assert tallied == sum(v.voting_power for v in vals.validators)
    with pytest.raises(ValueError, match="exceeds cap"):
        vals.verify_commit(CHAIN, BID, HEIGHT, commit)
    vals._total = None
    with pytest.raises(ValueError, match="exceeds cap"):
        vals.plan_commit_light(CHAIN, BID, HEIGHT, commit)


def test_trusting_builds_its_batch_from_the_columns(structured):
    """plan_commit_trusting keeps its address loop; its sign bytes come
    from the one CommitSignBatch body, by commit slot."""
    rng = random.Random(9)
    newer = _valset([rng.randrange(1, 50) for _ in range(N)])
    commit = _commit(newer, rng, absent=0.2, nil=0.2)
    # the trusted set: every other validator of the newer one
    trusted = ValidatorSet([v for v in newer.validators[::2]])
    plan = trusted.plan_commit_trusting(CHAIN, commit, 1, 3)
    assert plan.form == "trusting" and plan.lanes != plan.slots
    for lane, slot in zip(plan.lanes, plan.slots):
        assert trusted.validators[lane].address == \
            commit.signatures[slot].validator_address
    _same_msgs(plan.msgs, ref_msgs(CHAIN, commit, plan.slots))


@pytest.mark.parametrize("seed", range(6))
def test_build_patches_equals_the_old_builder(seed):
    """Many template groups and every varint width in one batch (the
    vote scheduler's and the arena's shape): byte-equal patches."""
    rng = np.random.default_rng(seed)
    n, k = 300, 7
    pre_len = rng.integers(40, 120, k).astype(np.int32)
    suf_len = rng.integers(0, 60, k).astype(np.int32)
    group = rng.integers(0, k, n).astype(np.int32)
    secs = rng.choice([0, 1, 127, 128, 1_753_928_000, (1 << 33) + 5], n)
    nanos = rng.choice([0, 1, 127, 128, 16_384, 2_097_152, 999_999_999], n)
    ts = (secs * 1_000_000_000 + nanos).astype(np.int64)
    if seed == 0:
        ts[:] = 0
    if seed == 1:
        ts, group = ts[:0], group[:0]
    got = sbm._build_patches(pre_len, suf_len, group, ts)
    want = ref_build_patches(pre_len, suf_len, group, ts)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    with pytest.raises(ValueError, match="too long"):
        sbm._build_patches(pre_len + (1 << 14), suf_len,
                           np.zeros(1, np.int32), np.ones(1, np.int64))


# ------------------------------------------------- the per-set columns

class FakeTables:
    """Stands for ExpandedKeys: accepts every lane."""

    def __init__(self, pubkeys):
        self.pubkeys = list(pubkeys)
        self.tables = np.zeros((len(self.pubkeys), 4), np.int32)

    def verify_structured(self, lanes, msgs, sigs):
        return np.ones(len(lanes), bool)

    def verify(self, lanes, msgs, sigs):
        return np.ones(len(lanes), bool)


@pytest.fixture
def tables(monkeypatch):
    """The real table cache (get_expanded, _CACHE, its LRU) over fake
    tables; yields the (pubkeys, digest) each lookup was handed."""
    _every_set_takes_the_expanded_path(monkeypatch)
    monkeypatch.setattr(ex, "ExpandedKeys", FakeTables)
    saved = dict(ex._CACHE)
    ex._CACHE.clear()
    seen = []
    real = ex.get_expanded

    def get_expanded(pubkeys, digest=None):
        seen.append((list(pubkeys), digest))
        return real(pubkeys, digest)

    monkeypatch.setattr(ex, "get_expanded", get_expanded)
    yield seen
    ex._CACHE.clear()
    ex._CACHE.update(saved)


def _fresh_digest(vals):
    return hashlib.sha256(
        b"".join(v.pub_key.bytes() for v in vals.validators)).digest()


def _verify_once(vals, rng=None):
    commit = _commit(vals, rng or random.Random(3), absent=0.0, nil=0.0)
    TRACER.clear()
    vals.verify_commit(CHAIN, BID, HEIGHT, commit)
    (span,) = [r for r in TRACER.snapshot()
               if r[0] == tracing.VERIFY_TABLES]
    return span[6]["digest"]


def _change_set(vals):
    extra = Validator.new(_keys(1, b"joins")[0], 7)
    gone = vals.validators[3].copy()
    gone.voting_power = 0
    vals.update_with_change_set([extra, gone])
    return vals


def _copy(vals):
    return vals.copy()


def _assign(vals):
    vals.validators = [v.copy() for v in vals.validators[:-2]]
    vals._total = None
    return vals


def _append(vals):
    vals.validators.append(Validator.new(_keys(1, b"appended")[0], 1))
    vals._total = None
    return vals


@pytest.mark.parametrize("change,held_after", [
    (_change_set, False), (_copy, True), (_assign, False),
    (_append, False)], ids=["update_with_change_set", "copy",
                            "assign_validators", "append_validator"])
def test_set_columns_follow_the_set(tables, change, held_after):
    vals = _valset([5] * 12, b"follow")
    assert _verify_once(vals) == "hashed"
    assert _verify_once(vals) == "held"
    assert tables[-1][1] == _fresh_digest(vals)

    changed = change(vals)
    assert _verify_once(changed) == ("held" if held_after else "hashed")
    keys = [v.pub_key.bytes() for v in changed.validators]
    assert tables[-1] == (keys, _fresh_digest(changed))
    cols = changed._columns()
    assert cols.addresses == [v.address for v in changed.validators]
    assert cols.power.tolist() == [v.voting_power
                                   for v in changed.validators]
    assert _verify_once(changed) == "held"
    assert tables[-1][1] == _fresh_digest(changed)


def test_a_set_with_another_key_type_keeps_its_ed25519_lanes_on_the_tables(
        tables):
    """One key of another type no longer takes the set off the tables:
    they are built over its ed25519 keys, and only that lane leaves."""
    from tendermint_tpu.crypto import secp256k1

    vals = _valset([5] * 6, b"mixed")
    other = secp256k1.Secp256k1PrivKey.from_secret(b"k1").pub_key()
    vals.validators = vals.validators + [Validator.new(other, 5)]
    vals._total = None
    cols = vals._columns()
    assert not cols.all_ed25519
    assert cols.ed_keys == cols.pubkeys[:6] and len(cols.pubkeys) == 7
    assert cols.ed_row.tolist() == [0, 1, 2, 3, 4, 5, -1]
    assert vals._use_expanded(range(3))
    assert not vals._use_expanded([6])      # the secp256k1 lane alone
    ok, verdicts = vals._batch_verify_lanes(
        [0, 6, 3], [b"a", b"b", b"c"], [b"\0" * 64] * 3)
    # the fake tables accept every lane; the host refuses the other
    assert verdicts.tolist() == [True, False, True] and not ok
    assert tables[-1] == (cols.ed_keys, ex.key_digest(cols.ed_keys))


def test_the_launch_is_called_from_the_ladders_own_frame(tables, monkeypatch):
    """Every verify site of an all-ed25519 set reaches the tables'
    launch straight from _batch_verify_lanes, as at PR 34. With a
    helper of their own between the ladder and the launch the first
    lowering of every structured shape took 40-42 s on the chip's host
    where it takes 15 (PERF.md section 6, PR 35: three runs against
    three), so the ed25519 rungs stay in the ladder's frame."""
    import sys

    callers = []

    def launch(self, lanes, msgs, sigs):
        callers.append(sys._getframe(1).f_code.co_name)
        return np.ones(len(lanes), bool)

    monkeypatch.setattr(FakeTables, "verify_structured", launch)
    monkeypatch.setattr(FakeTables, "verify", launch)
    vals = _valset([5] * 12, b"frames")
    _verify_once(vals)
    vals._batch_verify_lanes([0, 3], [b"a", b"b"], [b"\0" * 64] * 2)
    assert callers == ["_batch_verify_lanes"] * 2


def test_third_set_evicts_the_first_sets_tables(tables):
    """A ValidatorSet holds the cache's key, never the tables: the LRU
    of two still decides when a set's tables leave the chip."""
    sets = [_valset([3] * 8, tag) for tag in (b"s1", b"s2", b"s3")]
    digests = [_fresh_digest(s) for s in sets]
    for s in sets[:2]:
        _verify_once(s)
    assert list(ex._CACHE) == digests[:2]
    first_tables = ex._CACHE[digests[0]]
    _verify_once(sets[2])
    assert list(ex._CACHE) == digests[1:]   # the first set's are gone
    assert sets[0]._columns().digest == digests[0]  # its key is held
    for s in sets:
        held = list(vars(s).values()) + list(vars(s._columns()).values())
        assert not any(isinstance(v, FakeTables) for v in held)
    # verifying with the first set again builds its tables anew
    assert _verify_once(sets[0]) == "held"
    assert list(ex._CACHE) == [digests[2], digests[0]]
    assert ex._CACHE[digests[0]] is not first_tables


def test_get_expanded_hashes_only_when_handed_no_digest(tables, monkeypatch):
    keys = [k.bytes() for k in _keys(5, b"hash")]
    built = ex.get_expanded(keys)
    assert list(ex._CACHE) == [ex.key_digest(keys)] == \
        [hashlib.sha256(b"".join(keys)).digest()]
    monkeypatch.setattr(ex, "key_digest", lambda pubkeys: 1 / 0)
    assert ex.get_expanded(keys, hashlib.sha256(b"".join(keys)).digest()) \
        is built


# ------------------------------------------ nothing read is remembered

def test_a_commit_changed_in_place_is_read_again():
    """Real signatures, the host path: the refusal names the slots
    changed since the Commit was last accepted."""
    from helpers import make_genesis_state_and_pvs, sign_commit

    state, pvs = make_genesis_state_and_pvs(7)
    vs, chain, bid = state.validators, state.chain_id, BID
    commit = sign_commit(vs, pvs[:2] + pvs[3:], chain, 5, 0, bid, BASE_TS)
    assert sum(cs.is_absent() for cs in commit.signatures) == 1
    vs.verify_commit(chain, bid, 5, commit)
    vs.verify_commit_light(chain, bid, 5, commit)

    absent = [cs.is_absent() for cs in commit.signatures].index(True)
    a, b, c, d = [i for i in range(7) if i != absent][:4]
    sig = commit.signatures[a].signature
    commit.signatures[a].signature = sig[:-1] + bytes([sig[-1] ^ 1])
    commit.signatures[b].timestamp += 1
    refusal = rf"invalid signature\(s\) at index\(es\) \[{a}, {b}\]"
    with pytest.raises(VerificationError, match=refusal):
        vs.verify_commit(chain, bid, 5, commit)
    with pytest.raises(VerificationError, match=refusal):
        vs.verify_commit_light(chain, bid, 5, commit)

    commit.signatures[a].signature = sig
    commit.signatures[b].timestamp -= 1
    vs.verify_commit(chain, bid, 5, commit)
    commit.signatures[c].validator_address = b"\x00" * 20
    with pytest.raises(VerificationError,
                       match=f"wrong validator address in slot {c}"):
        vs.verify_commit(chain, bid, 5, commit)
    commit.signatures[c] = CommitSig.absent()
    commit.signatures[d] = CommitSig.absent()
    with pytest.raises(VerificationError,
                       match="insufficient voting power: 40 of 70"):
        vs.verify_commit(chain, bid, 5, commit)
