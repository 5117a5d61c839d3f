"""The program spans beneath the three host gaps (verify site, apply
path, admission and height), the folding leaf form, compile_cache on
expanded launch records, the kernels' phase names and
GET /debug/profile.

No kernel is compiled here: the verify sites run against fake device
programs (the launch sites, spans and ledger records around them are
the real ones), the apply path against sqlite under tmp_path."""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER, Tracer

from helpers import (
    commit_for, make_genesis, make_genesis_state_and_pvs, next_block,
    sign_commit,
)

NEW_KINDS = (
    "verify.commit", "verify.collect", "verify.sign_batch",
    "verify.tables", "verify.window",
    "state.validate", "state.exec", "state.save_responses",
    "state.app_commit", "state.save", "state.events",
    "store.save_block", "db.write",
    "consensus.new_height", "admission.queue_wait", "admission.flush",
    "crypto.table_build", "crypto.table_wait", "sync.window_cut",
    "state.valset_row",
    "verify.lane_split", "crypto.sr_merlin",
    "evidence.check", "evidence.collect", "evidence.update",
    "validate.block", "validate.basic", "validate.set_hashes",
    "validate.median_time", "state.update",
    "store.encode_commits", "store.encode_parts", "store.write",
)
APPLY_CHILDREN = [
    "state.validate", "state.exec", "state.save_responses",
    "state.update", "state.app_commit", "state.save", "state.events",
]


def run(coro):
    return asyncio.run(coro)


def children(recs, parent):
    """Records whose parent_id is `parent`'s span id, by start."""
    return sorted((r for r in recs if r[2] == parent[1]),
                  key=lambda r: r[4])


def inside(child, parent) -> bool:
    return parent[4] <= child[4] and \
        child[4] + child[5] <= parent[4] + parent[5]


def ancestors(recs, rec):
    by_id = {r[1]: r for r in recs}
    out = []
    while rec[2] in by_id:
        rec = by_id[rec[2]]
        out.append(rec[0])
    return out


# ------------------------------------------------------------- registry


@pytest.mark.parametrize("kind", NEW_KINDS)
def test_new_kind_is_registered_and_required(kind):
    from tools.check_spans import REQUIRED_KINDS, missing_required_kinds

    assert kind in tracing.registered_kinds()
    # the benchmark's per-layer readers find these by name
    assert kind in REQUIRED_KINDS
    assert missing_required_kinds() == []


def test_kinds_no_site_begins_are_gone():
    kinds = tracing.registered_kinds()
    assert "consensus.new_round" not in kinds
    assert "consensus.precommit_wait" not in kinds
    # the five steps ConsensusState._new_step enters still resolve
    for step in ("PROPOSE", "PREVOTE", "PREVOTE_WAIT", "PRECOMMIT",
                 "COMMIT"):
        assert tracing.consensus_step_kind(step) in kinds


# ----------------------------------------------------------- leaf / begin


def test_leaf_folds_a_run_into_one_entry():
    import time

    t = Tracer(capacity=64)
    with t.span(tracing.STATE_EXEC) as parent:
        for i in range(5):
            t0 = time.perf_counter_ns()
            t.leaf(tracing.DB_WRITE, t0, ops=1, bytes=10 + i)
    recs = t.snapshot()
    writes = [r for r in recs if r[0] == tracing.DB_WRITE]
    assert len(writes) == 1
    w = writes[0]
    assert w[2] == parent.span_id
    assert w[6]["n"] == 5 and w[6]["ops"] == 5
    assert w[6]["bytes"] == sum(10 + i for i in range(5))
    assert 0 < w[6]["busy_ns"] <= w[5]
    assert inside(w, next(r for r in recs if r[0] == tracing.STATE_EXEC))


def test_leaf_does_not_fold_across_parents_pauses_or_kinds():
    import time

    t = Tracer(capacity=64)
    now = time.perf_counter_ns
    with t.span(tracing.STATE_EXEC):
        t.leaf(tracing.DB_WRITE, now(), ops=1)
    with t.span(tracing.STATE_SAVE):
        t.leaf(tracing.DB_WRITE, now(), ops=1)   # another parent
        with t.span(tracing.CRYPTO_PACK):
            pass                                  # another kind between
        t.leaf(tracing.DB_WRITE, now(), ops=1)
        # a repeat begun long after the last one ended stands alone
        last = t.snapshot()[-1]
        t.leaf(tracing.DB_WRITE,
               last[4] + last[5] + tracing.LEAF_FOLD_NS + 1, ops=1)
    writes = [r for r in t.snapshot() if r[0] == tracing.DB_WRITE]
    assert len(writes) == 4
    assert all("n" not in w[6] for w in writes)


def test_keyed_leaf_folds_across_other_spans():
    """`fold_key`: a site whose repeats interleave with other spans
    (a vote's receive between the scheduler's batches) folds into the
    entry its key wrote last, under the parent it names, and units
    that overlap (stamped before the last one ended) fold too."""
    import time

    t = Tracer(capacity=256)
    now = time.perf_counter_ns
    root = t.begin(tracing.CONSENSUS_HEIGHT, parent=tracing.NOOP_SPAN)
    for i in range(6):
        with t.span(tracing.CRYPTO_PACK):      # another span between
            t0 = now()
        t.leaf(tracing.CONSENSUS_RECEIVE, t0 - 1000, fold_key="a",
               parent=root)
        t.leaf(tracing.CONSENSUS_HAS_VOTE, now(), fold_key="b",
               parent=root)
    recs = t.snapshot()
    for kind in (tracing.CONSENSUS_RECEIVE, tracing.CONSENSUS_HAS_VOTE):
        (rec,) = [r for r in recs if r[0] == kind]
        assert rec[2] == root.span_id and rec[6]["n"] == 6
        assert 0 < rec[6]["busy_ns"] <= rec[5]
    assert sum(r[0] == tracing.CRYPTO_PACK for r in recs) == 6
    # the entry keeps the place its first unit took
    assert [r[0] for r in recs][:3] == [
        tracing.CRYPTO_PACK, tracing.CONSENSUS_RECEIVE,
        tracing.CONSENSUS_HAS_VOTE]


@pytest.mark.parametrize("cut", ["parent", "pause", "lookback", "clear"])
def test_keyed_leaf_run_ends(cut):
    """What ends a keyed run: another parent, a pause past
    LEAF_FOLD_NS, LEAF_KEY_LOOKBACK spans sealed since it began, a
    cleared ring."""
    import time

    t = Tracer(capacity=256)
    now = time.perf_counter_ns
    a = t.begin(tracing.CONSENSUS_HEIGHT, parent=tracing.NOOP_SPAN)
    b = t.begin(tracing.CONSENSUS_HEIGHT, parent=tracing.NOOP_SPAN)
    t.leaf(tracing.CONSENSUS_RECEIVE, now(), fold_key="k", parent=a)
    t.leaf(tracing.CONSENSUS_RECEIVE, now(), fold_key="k", parent=a)
    start, parent = now(), a
    if cut == "parent":
        parent = b
    elif cut == "pause":
        last = t.snapshot()[-1]
        start = last[4] + last[5] + tracing.LEAF_FOLD_NS + 1
    elif cut == "lookback":
        for _ in range(tracing.LEAF_KEY_LOOKBACK):
            with t.span(tracing.CRYPTO_PACK):
                pass
        start = now()
    else:
        t.clear()
    t.leaf(tracing.CONSENSUS_RECEIVE, start, fold_key="k", parent=parent)
    t.leaf(tracing.CONSENSUS_RECEIVE, now() if cut != "pause" else start,
           fold_key="k", parent=parent)       # the new run folds on
    got = [(r[6] or {}).get("n", 1) for r in t.snapshot()
           if r[0] == tracing.CONSENSUS_RECEIVE]
    assert got == ([2] if cut == "clear" else [2, 2])


def test_leaf_counts_a_drop_when_the_ring_is_full():
    import time

    t = Tracer(capacity=4)
    for _ in range(4):
        with t.span(tracing.CRYPTO_PACK):
            pass
    t.leaf(tracing.DB_WRITE, time.perf_counter_ns(), ops=1)
    assert len(t) == 4 and t.dropped == 1
    t.leaf(tracing.DB_WRITE, time.perf_counter_ns(), ops=1)  # folds
    assert len(t) == 4 and t.dropped == 1
    with pytest.raises(ValueError):
        t.leaf("db.nope", time.perf_counter_ns())
    off = Tracer(enabled=False)
    off.leaf(tracing.DB_WRITE, time.perf_counter_ns())
    assert len(off) == 0


def test_leaf_folding_beside_another_thread_loses_and_reorders_nothing():
    """The fold rewrites the ring's newest entry: under the tracer's
    lock, so a span another thread seals meanwhile is neither
    overwritten nor moved."""
    import threading
    import time

    t = Tracer(capacity=1 << 16)
    n = 4000
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            t.leaf(tracing.DB_WRITE, time.perf_counter_ns(), ops=1)

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(n):
            with t.span(tracing.CRYPTO_PACK):
                pass
            assert len(t.snapshot()) >= 1
    finally:
        stop.set()
        th.join()
    recs = t.snapshot()
    packs = [r for r in recs if r[0] == tracing.CRYPTO_PACK]
    assert len(packs) == n and t.dropped == 0
    ends = [r[4] + r[5] for r in packs]
    assert ends == sorted(ends)
    writes = [r for r in recs if r[0] == tracing.DB_WRITE]
    assert sum(w[6].get("n", 1) for w in writes) == sum(
        w[6]["ops"] for w in writes)


def test_resize_keeps_the_newest_spans():
    t = Tracer(capacity=4)
    for _ in range(4):
        with t.span(tracing.CRYPTO_PACK):
            pass
    ids = [r[1] for r in t.snapshot()]
    t.resize(8)
    for _ in range(4):
        with t.span(tracing.CRYPTO_PACK):
            pass
    assert t.capacity == 8 and len(t) == 8 and t.dropped == 0
    assert [r[1] for r in t.snapshot()][:4] == ids
    t.resize(2)
    assert len(t) == 2 and [r[1] for r in t.snapshot()][0] > ids[-1]


def test_begin_backdates_to_a_stamp():
    import time

    t = Tracer(capacity=8)
    stamp = time.perf_counter_ns() - 5_000_000
    t.begin(tracing.ADMISSION_QUEUE_WAIT, start_ns=stamp, lanes=3).end()
    (rec,) = t.snapshot()
    assert rec[4] == stamp and rec[5] >= 5_000_000
    assert rec[6] == {"lanes": 3}


# ------------------------------------------------------------ verify site


def _fake_expanded(monkeypatch, vals):
    """An ExpandedKeys for `vals` with no tables and a structured
    program that accepts every lane: the host side of the launch
    (prepare, shard args, compile count, spans, ledger) is the real one."""
    import tendermint_tpu.types.validator_set as vs_mod
    from tendermint_tpu.crypto.tpu import expanded as ex

    keys = object.__new__(ex.ExpandedKeys)
    keys.pubkeys = tuple(v.pub_key.bytes() for v in vals.validators)
    keys.sharded = False
    keys.mesh = None
    n = len(keys.pubkeys)
    keys.akeys = np.zeros((n, 32), np.uint8)
    keys.key_ok = np.ones(n, bool)
    keys.tables = np.zeros((n, 4), np.int32)
    keys._maybe_reshard = lambda: None
    monkeypatch.setattr(vs_mod, "_EXPAND_MIN", 2)
    monkeypatch.setattr(ex, "get_expanded",
                        lambda pubkeys, digest=None: keys)
    monkeypatch.setattr(ex, "max_keys", lambda: 1 << 20)
    monkeypatch.setattr(
        ex, "_skernel",
        lambda wpi=None: lambda *, idx, width, **kw: np.ones(
            idx.shape[0], bool))
    return keys


def _commit(n_vals=4, height=3):
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    state, pvs = make_genesis_state_and_pvs(n_vals)
    bid = BlockID(bytes([height]) * 32,
                  PartSetHeader(1, bytes([height]) * 32))
    commit = sign_commit(state.validators, pvs, state.chain_id, height, 0,
                         bid, 1_700_000_000 * 10**9 + height)
    return state, bid, commit


def test_verify_commit_span_tree_and_compile_cache(monkeypatch):
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.tpu import ledger
    from tendermint_tpu.crypto.tpu import verify as tv

    cbatch.reset_breakers()
    state, bid, commit = _commit()
    vals = state.validators
    _fake_expanded(monkeypatch, vals)
    # a shape no other test of this process has launched
    monkeypatch.setattr(tv, "_COMPILED_SHAPES", {})
    ledger.reset()
    TRACER.clear()
    vals.verify_commit(state.chain_id, bid, 3, commit)
    vals.verify_commit(state.chain_id, bid, 3, commit)

    recs = TRACER.snapshot()
    roots = [r for r in recs if r[0] == tracing.VERIFY_COMMIT]
    assert len(roots) == 2
    for root in roots:
        assert root[2] == 0
        assert root[6] == {"form": "full", "lanes": 4, "structured": True}
        kids = children(recs, root)
        assert [k[0] for k in kids] == [
            tracing.VERIFY_COLLECT, tracing.VERIFY_SIGN_BATCH,
            tracing.VERIFY_TABLES, tracing.CRYPTO_VERIFY]
        assert all(inside(k, root) for k in kids)
        # children follow one another; what is left is verdict
        # handling, verify.commit's own time
        for a, b in zip(kids, kids[1:]):
            assert a[4] + a[5] <= b[4]
        launch = kids[-1]
        assert {k[0] for k in children(recs, launch)} >= {
            tracing.CRYPTO_PACK, tracing.CRYPTO_DISPATCH,
            tracing.CRYPTO_READBACK}

    # the structured launch's record says what count_compile answered
    launches = [r for r in ledger.snapshot() if r["kernel"] == "structured"]
    assert [r["compile_cache"] for r in launches] == ["miss", "hit"]


def _mixed_commit(monkeypatch, n_ed=4, n_sr=4, height=3):
    """A set of both key types, its commit, and fake device programs
    for both: tables over the ed25519 keys and an sr25519 kernel that
    accept every lane."""
    import hashlib

    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey
    from tendermint_tpu.crypto.tpu import sr_verify
    from tendermint_tpu.state import make_genesis_state
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.priv_validator import MockPV
    from tendermint_tpu.types.validator_set import ValidatorSet

    pvs = [MockPV((Sr25519PrivKey if i >= n_ed else Ed25519PrivKey)(
        hashlib.sha256(b"site%d" % i).digest()))
        for i in range(n_ed + n_sr)]
    gdoc = GenesisDoc(chain_id="mixed-sites", genesis_time=1,
                      validators=[GenesisValidator(pv.get_pub_key(), 10)
                                  for pv in pvs])
    gdoc.validate_and_complete()
    state = make_genesis_state(gdoc)
    vals = state.validators
    ed_only = ValidatorSet([v for v in vals.validators
                            if v.pub_key.type_name == "ed25519"])
    ed_only.validators = [v for v in vals.validators
                          if v.pub_key.type_name == "ed25519"]
    _fake_expanded(monkeypatch, ed_only)
    monkeypatch.setattr(
        sr_verify, "_kernel",
        lambda: lambda *, ab, **kw: np.ones(ab.shape[0], bool))
    bid = BlockID(bytes([height]) * 32,
                  PartSetHeader(1, bytes([height]) * 32))
    commit = sign_commit(vals, pvs, state.chain_id, height, 0, bid,
                         1_700_000_000 * 10**9 + height)
    return state, pvs, bid, commit


def test_lane_split_and_sr_merlin_at_their_sites(monkeypatch):
    from tendermint_tpu.crypto import batch as cbatch

    cbatch.reset_breakers()
    state, _, bid, commit = _mixed_commit(monkeypatch)
    TRACER.clear()
    state.validators.verify_commit(state.chain_id, bid, 3, commit)
    recs = TRACER.snapshot()
    (root,) = [r for r in recs if r[0] == tracing.VERIFY_COMMIT]
    assert root[6] == {"form": "full", "lanes": 8, "structured": True}
    assert [k[0] for k in children(recs, root)] == [
        tracing.VERIFY_COLLECT, tracing.VERIFY_SIGN_BATCH,
        tracing.VERIFY_TABLES, tracing.CRYPTO_VERIFY, tracing.CRYPTO_BATCH]
    # the partition is the sign-bytes step's: one a batch, not two
    (split,) = [r for r in recs if r[0] == tracing.VERIFY_LANE_SPLIT]
    assert ancestors(recs, split)[0] == tracing.VERIFY_SIGN_BATCH
    assert split[6] == {"ed25519": 4, "sr25519": 4, "other": 0}
    (tables,) = [r for r in recs if r[0] == tracing.VERIFY_TABLES]
    assert tables[6]["keys"] == 4
    (merlin,) = [r for r in recs if r[0] == tracing.CRYPTO_SR_MERLIN]
    assert merlin[6] == {"lanes": 4, "groups": 1, "blocks": 4}
    assert ancestors(recs, merlin)[:2] == [tracing.CRYPTO_BATCH,
                                           tracing.VERIFY_COMMIT]
    assert not [r for r in recs if r[0] == tracing.CRYPTO_HOST_VERIFY]

    # full bytes handed straight to the ladder: split there, once
    vals = state.validators
    lanes = list(range(8))
    TRACER.clear()
    vals._batch_verify_lanes(
        lanes, [commit.vote_sign_bytes(state.chain_id, s) for s in lanes],
        [cs.signature for cs in commit.signatures])
    assert len([r for r in TRACER.snapshot()
                if r[0] == tracing.VERIFY_LANE_SPLIT]) == 1


def test_all_ed25519_set_opens_no_lane_split(monkeypatch):
    from tendermint_tpu.crypto import batch as cbatch

    cbatch.reset_breakers()
    state, bid, commit = _commit()
    _fake_expanded(monkeypatch, state.validators)
    TRACER.clear()
    state.validators.verify_commit(state.chain_id, bid, 3, commit)
    state.validators.verify_commit_light(state.chain_id, bid, 3, commit)
    kinds = {r[0] for r in TRACER.snapshot()}
    assert tracing.VERIFY_LANE_SPLIT not in kinds
    assert tracing.CRYPTO_SR_MERLIN not in kinds


def test_evidence_spans_at_their_sites(monkeypatch):
    from types import SimpleNamespace

    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.evidence import Pool
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.state.store import Store
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.evidence import DuplicateVoteEvidence
    from tendermint_tpu.types.vote import Vote, VoteType

    cbatch.reset_breakers()
    state, pvs, _, _ = _mixed_commit(monkeypatch)
    vals = state.validators
    store = Store(MemDB())
    store.save_validator_set(1, vals)
    state.last_block_height, state.last_block_time = 1, 50
    store.save(state)
    blocks = SimpleNamespace(load_block_meta=lambda h: SimpleNamespace(
        header=SimpleNamespace(time=50)))
    pool = Pool(MemDB(), store, blocks)

    def vote(pv, tag):
        idx, val = vals.get_by_address(pv.get_pub_key().address())
        v = Vote(type=VoteType.PREVOTE, height=1, round=0,
                 block_id=BlockID(bytes([tag]) * 32,
                                  PartSetHeader(1, bytes([tag]) * 32)),
                 timestamp=40, validator_address=val.address,
                 validator_index=idx)
        pv.sign_vote(state.chain_id, v)
        return v

    evs = [DuplicateVoteEvidence.from_votes(vote(pv, 1), vote(pv, 2), 50,
                                            vals) for pv in pvs]
    TRACER.clear()
    pool.check_evidence(evs)
    recs = TRACER.snapshot()
    (check,) = [r for r in recs if r[0] == tracing.EVIDENCE_CHECK]
    assert check[6] == {"evidence": 8, "heights": 1, "sets": 1,
                        "lanes": 16}
    kids = [k[0] for k in children(recs, check)]
    assert kids[0] == tracing.EVIDENCE_COLLECT
    # after the collecting: the sign bytes' split, then the launches
    assert kids[1] == tracing.VERIFY_LANE_SPLIT
    assert tracing.CRYPTO_BATCH in kids[2:]
    (merlin,) = [r for r in recs if r[0] == tracing.CRYPTO_SR_MERLIN]
    assert tracing.EVIDENCE_CHECK in ancestors(recs, merlin)
    assert merlin[6]["lanes"] == 8

    TRACER.clear()
    pool.update(state, evs)
    pool.update(state, [])            # a block without evidence: none
    (upd,) = [r for r in TRACER.snapshot()
              if r[0] == tracing.EVIDENCE_UPDATE]
    assert upd[6] == {"committed": 8}


def test_verify_commit_light_and_trusting_forms(monkeypatch):
    from tendermint_tpu.crypto import batch as cbatch

    cbatch.reset_breakers()
    state, bid, commit = _commit()
    vals = state.validators
    _fake_expanded(monkeypatch, vals)
    TRACER.clear()
    vals.verify_commit_light(state.chain_id, bid, 3, commit)
    vals.verify_commit_light_trusting(state.chain_id, commit, 1, 3)
    recs = TRACER.snapshot()
    forms = [r[6]["form"] for r in recs if r[0] == tracing.VERIFY_COMMIT]
    assert forms == ["light", "trusting"]
    for root in (r for r in recs if r[0] == tracing.VERIFY_COMMIT):
        assert root[6]["lanes"] >= 2 and root[6]["structured"] is True
        assert [k[0] for k in children(recs, root)] == [
            tracing.VERIFY_TABLES, tracing.CRYPTO_VERIFY]
    # the selection half (plan_commit_*) is spanned too, before execute
    assert [r[0] for r in recs if r[0] in (
        tracing.VERIFY_COLLECT, tracing.VERIFY_SIGN_BATCH)] == [
        tracing.VERIFY_COLLECT, tracing.VERIFY_SIGN_BATCH] * 2


def test_verify_window_span_has_its_building_apart_from_its_launch(
        monkeypatch):
    from tendermint_tpu.blockchain.verify_ahead import WindowPipeline
    from tendermint_tpu.crypto import batch as cbatch

    cbatch.reset_breakers()
    state, pvs = make_genesis_state_and_pvs(4)
    _fake_expanded(monkeypatch, state.validators)
    blocks, last_commit, st = [], None, state
    for _ in range(4):
        block, bid = next_block(st, pvs, last_commit, [b"k=v"])
        last_commit = commit_for(st, pvs, block, bid)
        blocks.append(block)
        # only the header chain matters to the window: keep the valset
        st = st.copy() if hasattr(st, "copy") else st
        st.last_block_height = block.header.height
        st.last_block_id = bid
        st.last_block_time = block.header.time
    TRACER.clear()
    items, parts, results = WindowPipeline._verify_window_job(
        state.validators, state.chain_id, blocks)
    assert len(items) == len(parts) == len(results) == 3
    recs = TRACER.snapshot()
    (window,) = [r for r in recs if r[0] == tracing.VERIFY_WINDOW]
    assert window[6]["blocks"] == 3 and window[6]["lanes"] >= 3 * 3
    kids = children(recs, window)
    assert [k[0] for k in kids][:2] == [
        tracing.VERIFY_COLLECT, tracing.VERIFY_SIGN_BATCH]
    # the part sets are built before collect begins: window time that
    # is neither a child nor a launch
    assert kids[0][4] > window[4]
    assert tracing.VERIFY_WINDOW in ancestors(
        recs, next(r for r in recs if r[0] == tracing.CRYPTO_VERIFY))


# ------------------------------------------------------------ table builds


def test_table_build_and_wait_spans(monkeypatch):
    """One thread builds a set's tables, a launch site that asks for
    the same set meanwhile waits for it: one crypto.table_build (keys,
    bytes, evicted, thread) and one crypto.table_wait that ends when
    the build does; a third set pushes the first out of the cache."""
    import threading
    import time
    from collections import OrderedDict

    from tendermint_tpu.crypto.tpu import expanded as ex

    started = threading.Event()

    class SlowTables:
        def __init__(self, pubkeys):
            self.tables = np.zeros((len(pubkeys), 8), np.int32)
            started.set()
            time.sleep(0.15)

    monkeypatch.setattr(ex, "ExpandedKeys", SlowTables)
    monkeypatch.setattr(ex, "_CACHE", OrderedDict())
    sets = [[bytes([i, j]) * 16 for j in range(3)] for i in range(3)]
    TRACER.clear()
    builder = ex.warm_async(sets[0])
    assert started.wait(5)
    got = ex.get_expanded(sets[0])            # waits for the builder
    builder.join()
    assert got is ex.get_expanded(sets[0])    # a hit: no span
    ex.get_expanded(sets[1])
    ex.get_expanded(sets[2])                  # evicts sets[0]'s
    recs = TRACER.snapshot()
    builds = [r for r in recs if r[0] == tracing.CRYPTO_TABLE_BUILD]
    waits = [r for r in recs if r[0] == tracing.CRYPTO_TABLE_WAIT]
    assert [b[6] for b in builds] == [
        {"keys": 3, "thread": "warm", "bytes": 96, "evicted": 0},
        {"keys": 3, "thread": "inline", "bytes": 96, "evicted": 0},
        {"keys": 3, "thread": "inline", "bytes": 96, "evicted": 1}]
    assert all(b[5] >= 150_000_000 for b in builds)
    (wait,) = waits
    assert wait[6] == {"keys": 3} and wait[5] > 50_000_000
    # the wait ends when the build it waited for ends
    assert abs((wait[4] + wait[5]) - (builds[0][4] + builds[0][5])) \
        < 50_000_000
    assert len(ex._CACHE) == ex._CACHE_MAX == 2


# -------------------------------------------------------------- apply path


def test_apply_block_children_in_order_and_db_writes(tmp_path,
                                                     monkeypatch):
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
    from tendermint_tpu.libs.db import SqliteDB
    from tendermint_tpu.state import make_genesis_state
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.store import Store
    from tendermint_tpu.store import BlockStore

    commits = {"n": 0}
    for name in ("set", "delete", "write_batch"):
        real = getattr(SqliteDB, name)

        def counted(self, *a, _real=real, **kw):
            commits["n"] += 1   # each is one durable commit
            return _real(self, *a, **kw)

        monkeypatch.setattr(SqliteDB, name, counted)

    async def go():
        gdoc, pvs = make_genesis(4)
        state = make_genesis_state(gdoc)
        dbs = [SqliteDB(str(tmp_path / f"{n}.sqlite"))
               for n in ("state", "blockstore", "app")]
        store = Store(dbs[0])
        store.save(state)
        block_store = BlockStore(dbs[1])
        client = LocalClient(PersistentKVStoreApp(dbs[2]))
        await client.start()
        executor = BlockExecutor(store, client)
        last_commit = None
        per_block = []
        for h in range(3):
            # the third block re-weights a validator: the set moves
            last = b"c%d=3" % h if h < 2 else b"val:%s!%d" % (
                pvs[1].get_pub_key().bytes().hex().encode(), 15)
            block, bid = next_block(state, pvs, last_commit,
                                    [b"a%d=1" % h, b"b%d=2" % h, last])
            seen = commit_for(state, pvs, block, bid)
            TRACER.clear()
            commits["n"] = 0
            block_store.save_block(block, block.make_part_set(), seen)
            state, _ = await executor.apply_block(state, bid, block)
            per_block.append((TRACER.snapshot(), commits["n"]))
            last_commit = seen
        await client.stop()
        for d in dbs:
            d.close()
        return per_block

    for height, (recs, durable) in enumerate(run(go()), 1):
        (apply_,) = [r for r in recs if r[0] == tracing.STATE_APPLY_BLOCK]
        kids = [k for k in children(recs, apply_)]
        assert [k[0] for k in kids] == APPLY_CHILDREN
        assert all(inside(k, apply_) for k in kids)
        for a, b in zip(kids, kids[1:]):
            assert a[4] + a[5] <= b[4]
        assert kids[1][6] == {"txs": 3}
        (saved,) = [r for r in recs if r[0] == tracing.STORE_SAVE_BLOCK]
        assert saved[4] + saved[5] <= apply_[4]
        # as many db.write as durable commits: one for each of the
        # stores' batches and ONE for the app's block, at Commit
        writes = [r for r in recs if r[0] == tracing.DB_WRITE]
        assert sum(w[6].get("n", 1) for w in writes) == durable >= 4
        by_parent = {}
        by_id = {r[1]: r[0] for r in recs}
        for w in writes:
            kind = by_id.get(w[2])
            by_parent[kind] = by_parent.get(kind, 0) + w[6].get("n", 1)
        # DeliverTx stages: nothing is written inside state.exec
        assert tracing.STATE_EXEC not in by_parent
        assert by_parent[tracing.STORE_WRITE] == 1
        assert by_parent[tracing.STATE_SAVE_RESPONSES] == 1
        assert by_parent[tracing.STATE_APP_COMMIT] == 1
        # the state's ONE batch; a set's membership is encoded into it
        # only by the block that moves the set (in force two on)
        assert by_parent[tracing.STATE_SAVE] == 1
        (save,) = [k for k in kids if k[0] == tracing.STATE_SAVE]
        rows = [r for r in recs if r[0] == tracing.STATE_VALSET_ROW]
        assert all(r[2] == save[1] for r in rows)
        assert [dict(r[6], bytes=0) for r in rows] == (
            [] if height < 3
            else [{"height": height + 2, "keys": 4, "bytes": 0}])
        assert all(r[6]["bytes"] > 4 * 52 for r in rows)
        # the app's commit carries the block: its keys (the third
        # block's val: tx is none) + its state record
        (app_write,) = [w for w in writes
                        if by_id.get(w[2]) == tracing.STATE_APP_COMMIT]
        assert app_write[6]["ops"] == (4 if height < 3 else 3)


def test_validate_block_runs_in_the_worker_beneath_state_validate(
        tmp_path):
    """state.validate is opened on the loop around the await;
    validate.block is the whole call inside the worker thread, so the
    parent less this child is the executor hop."""
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.state import make_genesis_state
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.store import Store

    async def go():
        gdoc, pvs = make_genesis(4)
        state = make_genesis_state(gdoc)
        store = Store(MemDB())
        store.save(state)
        client = LocalClient(KVStoreApp())
        await client.start()
        executor = BlockExecutor(store, client)
        last_commit = None
        for h in range(2):
            block, bid = next_block(state, pvs, last_commit, [b"a%d=1" % h])
            seen = commit_for(state, pvs, block, bid)
            TRACER.clear()
            state, _ = await executor.apply_block(state, bid, block)
            last_commit = seen
        await client.stop()
        return TRACER.snapshot()

    recs = run(go())   # the second block: it carries a LastCommit
    (outer,) = [r for r in recs if r[0] == tracing.STATE_VALIDATE]
    (whole,) = children(recs, outer)
    assert whole[0] == tracing.VALIDATE_BLOCK
    assert whole[6]["height"] == 2 and inside(whole, outer)
    assert whole[3] != outer[3]            # another thread than the loop's
    assert whole[6]["cpu_ns"] >= 0
    assert "cpu_ns" not in (outer[6] or {})   # it wraps an await
    kids = children(recs, whole)
    assert [k[0] for k in kids] == [
        tracing.VALIDATE_BASIC, tracing.VALIDATE_SET_HASHES,
        tracing.VERIFY_COMMIT, tracing.VALIDATE_MEDIAN_TIME]
    assert all(inside(k, whole) and k[3] == whole[3] for k in kids)
    assert kids[1][6]["validators"] == 4
    (update,) = [r for r in recs if r[0] == tracing.STATE_UPDATE]
    assert update[6]["updates"] == 0 and "cpu_ns" in update[6]


def _pinned_block(n_sigs=1000):
    """One fixed block of height 2 with an `n_sigs`-signature LastCommit
    and its seen commit: nothing in it moves with the clock or a seed
    (the store checks no signature, so the signatures are digests)."""
    import hashlib

    from tendermint_tpu.state import make_genesis_state
    from tendermint_tpu.types.block import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader,
    )
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    from helpers import deterministic_pv

    gdoc = GenesisDoc(
        chain_id="pinned-chain", genesis_time=1_700_000_000 * 10**9,
        validators=[GenesisValidator(deterministic_pv(i).get_pub_key(), 10)
                    for i in range(4)])
    gdoc.validate_and_complete()
    state = make_genesis_state(gdoc)

    def commit(height, tag):
        bid = BlockID(
            hashlib.sha256(b"block-%d" % height).digest(),
            PartSetHeader(1, hashlib.sha256(b"parts-%d" % height).digest()))
        return Commit(height, 0, bid, [
            CommitSig(BlockIDFlag.COMMIT,
                      hashlib.sha256(b"addr-%d" % i).digest()[:20],
                      1_700_000_001 * 10**9 + i,
                      hashlib.sha512(b"%s-%d-%d" % (tag, height, i)).digest())
            for i in range(n_sigs)])

    block = state.make_block(
        2, [b"k%d=v" % i for i in range(8)], commit(1, b"last"), [],
        state.validators.get_proposer().address, 1_700_000_002 * 10**9)
    return block, commit(2, b"seen")


def test_save_block_children_and_the_batch_it_writes(tmp_path):
    """store.save_block's three children cover it, and the batch handed
    to write_batch is the one the store wrote before the spans: its
    digest was taken at the commit before them (PR 36's), from this
    block."""
    import hashlib

    # the store imports it where it hits the failpoint, between two
    # children: a process's first save would time the import there
    from tendermint_tpu.libs import failpoints  # noqa: F401
    from tendermint_tpu.libs.db import SqliteDB
    from tendermint_tpu.store import BlockStore

    block, seen = _pinned_block()
    db = SqliteDB(str(tmp_path / "blockstore.sqlite"))
    batches = []
    real = db.write_batch

    def capture(ops):
        batches.append(list(ops))
        return real(ops)

    db.write_batch = capture
    store = BlockStore(db)
    parts = block.make_part_set()
    TRACER.clear()
    store.save_block(block, parts, seen)
    recs = TRACER.snapshot()
    assert store.load_seen_commit(2).signatures[999].signature == \
        seen.signatures[999].signature
    db.close()

    (ops,) = batches
    assert [k[:2] for k, _ in ops] == [b"H:", b"BH", b"SC", b"P:", b"P:",
                                      b"C:", b"bl"]
    digest = hashlib.sha256()
    for k, v in ops:
        digest.update(len(k).to_bytes(4, "big") + k
                      + len(v).to_bytes(4, "big") + v)
    assert digest.hexdigest() == ("5cecd69020e40e5bea71ec8ea99c7681"
                                  "e674b5596157437ffc4c545b7a7e7fc7")
    batch_bytes = sum(len(v) for _, v in ops)
    assert batch_bytes == 309678

    (whole,) = [r for r in recs if r[0] == tracing.STORE_SAVE_BLOCK]
    kids = children(recs, whole)
    assert [k[0] for k in kids] == [
        tracing.STORE_ENCODE_COMMITS, tracing.STORE_ENCODE_PARTS,
        tracing.STORE_WRITE]
    assert all(inside(k, whole) for k in kids)
    for a, b in zip(kids, kids[1:]):
        assert a[4] + a[5] <= b[4]
    commits, rows, write = (k[6] for k in kids)
    assert rows["parts"] == parts.total == 2 and write == {"rows": 7}
    # the thread's CPU on the box and its two pure-host children; the
    # write waits on the disk and feeds no CPU reading
    assert all(k[6]["cpu_ns"] >= 0 for k in (whole, kids[0], kids[1]))
    # both 1,000-signature commits came from their columns
    assert set(commits) == {"cpu_ns", "columnar"}
    assert commits["columnar"] == 2
    (commit_,) = children(recs, kids[2])     # the COMMIT, in store.write
    assert commit_[0] == tracing.DB_WRITE and commit_[6]["ops"] == 7
    # nothing of the store's work sits outside the three
    assert sum(k[5] for k in kids) >= 0.9 * whole[5]


@pytest.mark.parametrize("last_commit, columnar", [
    (None, 1),       # a replay cell's first block carries none
    ("empty", 2),    # consensus gives height 1 an empty Commit: no slot
                     # fits no column, so the array path writes nothing
    ("odd", 1),      # one slot of the last commit fits no column: the
                     # per-slot writer takes that commit, the seen
                     # commit still comes from its columns
])
def test_encode_commits_says_how_many_came_from_columns(
        tmp_path, last_commit, columnar):
    """store.encode_commits attr `columnar` counts the block's commits
    that types/sign_batch.py commit_sig_rows encoded, 0-2."""
    from tendermint_tpu.libs.db import SqliteDB
    from tendermint_tpu.store import BlockStore
    from tendermint_tpu.types.block import NIL_BLOCK_ID, Commit

    block, seen = _pinned_block(n_sigs=4)
    if last_commit is None:
        block.last_commit = None
    elif last_commit == "empty":
        block.last_commit = Commit(0, 0, NIL_BLOCK_ID, [])
    else:
        block.last_commit.signatures[2].validator_address = b"\x01" * 19
    db = SqliteDB(str(tmp_path / "blockstore.sqlite"))
    store = BlockStore(db)
    store.height = 1     # the pinned block is of height 2
    TRACER.clear()
    store.save_block(block, block.make_part_set(), seen)
    (span,) = [r for r in TRACER.snapshot()
               if r[0] == tracing.STORE_ENCODE_COMMITS]
    assert span[6]["columnar"] == columnar
    if last_commit is None:
        assert store.load_block_commit(1) is None
    else:
        assert store.load_block_commit(1) == block.last_commit
    assert store.load_seen_commit(2) == seen
    db.close()


# --------------------------------------------------------------- admission


def test_admission_spans_cut_reasons_and_lineage(monkeypatch):
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.mempool.admission import AdmissionCollector
    from tendermint_tpu.types import tx_envelope

    cbatch.reset_breakers()
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    monkeypatch.setattr(
        tv, "_kernel",
        lambda: lambda btab, **packed: np.ones(
            packed["s_ok"].shape[0], bool))
    signer = Ed25519PrivKey.from_secret(b"admission-span-signer")

    def env(i):
        return tx_envelope.parse(
            tx_envelope.sign_tx(signer, b"tx-%d" % i))

    async def go():
        col = AdmissionCollector(batch_max=4, flush_ms=30.0,
                                 device_threshold=1)
        try:
            # a request's span is current when the flusher starts: the
            # batch spans must not hang beneath it
            with TRACER.span(tracing.P2P_RECV_MSG):
                full = await asyncio.gather(
                    *(col.verify(env(i)) for i in range(4)))
            late = await col.verify(env(9))
            return full, late
        finally:
            col.close()

    TRACER.clear()
    full, late = run(go())
    assert all(full) and late
    recs = TRACER.snapshot()
    waits = [r for r in recs if r[0] == tracing.ADMISSION_QUEUE_WAIT]
    assert [(w[6]["lanes"], w[6]["cut"]) for w in waits] == [
        (4, "full"), (1, "deadline")]
    assert all(w[2] == 0 for w in waits)
    assert waits[1][5] >= 25_000_000          # it waited its deadline out
    assert waits[1][6]["wait_sum_ms"] >= 25.0
    assert waits[0][6]["wait_sum_ms"] >= 0.0
    flushes = [r for r in recs if r[0] == tracing.ADMISSION_FLUSH]
    assert [(f[6]["lanes"], f[6]["backend"]) for f in flushes] == [
        (4, "device"), (1, "device")]
    assert all(f[2] == 0 for f in flushes)
    for w, f in zip(waits, flushes):
        assert w[4] + w[5] <= f[4]             # the cut ends one, opens the other
    verifies = [r for r in recs if r[0] == tracing.CRYPTO_VERIFY]
    assert len(verifies) == 2
    for v in verifies:
        assert v[2] != 0
        assert tracing.ADMISSION_FLUSH in ancestors(recs, v)
        assert v[3] != flushes[0][3]           # it ran in a worker thread


def test_admission_flush_names_the_host_backend():
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.mempool.admission import AdmissionCollector
    from tendermint_tpu.types import tx_envelope

    signer = Ed25519PrivKey.from_secret(b"admission-span-signer")

    async def go():
        col = AdmissionCollector(batch_max=2, flush_ms=1.0,
                                 device_threshold=1 << 20)
        try:
            return await col.verify(tx_envelope.parse(
                tx_envelope.sign_tx(signer, b"small")))
        finally:
            col.close()

    TRACER.clear()
    assert run(go())
    (flush,) = [r for r in TRACER.snapshot()
                if r[0] == tracing.ADMISSION_FLUSH]
    assert flush[6] == {"lanes": 1, "backend": "host"}


def test_light_spans_cut_reasons_and_lineage(monkeypatch):
    """The light plane's batches leave the pair the admission plane's
    do (one collector emits both): light.queue_wait with the cut
    reason, light.flush with the backend, crypto.verify beneath it."""
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.light.serving import LightVerifyCollector

    from helpers import CHAIN_ID
    from test_light import LightChain

    cbatch.reset_breakers()
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    monkeypatch.setattr(
        tv, "_kernel",
        lambda: lambda btab, **packed: np.ones(
            packed["s_ok"].shape[0], bool))
    chain = LightChain(3)

    def plan(h):
        lb = chain.blocks[h]
        sh = lb.signed_header
        return lb.validator_set.plan_commit_light(
            CHAIN_ID, sh.commit.block_id, sh.header.height, sh.commit)

    plans = [plan(h) for h in (1, 2, 3)]
    assert [len(p) for p in plans] == [3, 3, 3]

    async def go():
        # (a cut leaves the sentinel's lane free: batch_max - 1)
        col = LightVerifyCollector(batch_max=7, flush_ms=30.0,
                                   device_threshold=1)
        try:
            with TRACER.span(tracing.P2P_RECV_MSG):
                await asyncio.gather(col.check(plans[0]),
                                     col.check(plans[1]))
            await col.check(plans[2])
        finally:
            col.close()

    TRACER.clear()
    run(go())
    recs = TRACER.snapshot()
    waits = [r for r in recs if r[0] == tracing.LIGHT_QUEUE_WAIT]
    assert [(w[6]["lanes"], w[6]["cut"]) for w in waits] == [
        (6, "full"), (3, "deadline")]
    assert all(w[2] == 0 for w in waits)
    assert waits[1][5] >= 25_000_000          # it waited its deadline out
    assert waits[1][6]["wait_sum_ms"] >= 25.0
    flushes = [r for r in recs if r[0] == tracing.LIGHT_FLUSH]
    assert [f[6] for f in flushes] == [
        {"lanes": 6, "backend": "device"},
        {"lanes": 3, "backend": "device"}]
    assert all(f[2] == 0 for f in flushes)
    for w, f in zip(waits, flushes):
        assert w[4] + w[5] <= f[4]
    verifies = [r for r in recs if r[0] == tracing.CRYPTO_VERIFY]
    assert len(verifies) == 2
    for v in verifies:
        assert tracing.LIGHT_FLUSH in ancestors(recs, v)
        assert v[3] != flushes[0][3]           # it ran in a worker thread


LIGHT_KINDS = {
    # kind: the sums a unit of the proxy's path leaves beside n, busy_ns
    tracing.LIGHT_REQUEST: ("hits", "coalesced", "misses", "failed"),
    tracing.LIGHT_FETCH: ("witness",),
    tracing.LIGHT_PLAN: ("lanes", "trusting"),
    tracing.LIGHT_STEP: ("adjacent", "pivots", "gap"),
    tracing.LIGHT_STORE_SAVE: (),
    tracing.LIGHT_DETECT: (),
}


def _light_requests(tmp_path=None):
    """A proxy on its plane, two witnesses, asked over TCP for the
    latest header, for a height between the trusted ones, for that
    height again and for one that does not exist: the ring's entries of
    the light kinds, and the plane."""
    from tendermint_tpu.config import LightConfig
    from tendermint_tpu.light import ServingPool
    from tendermint_tpu.rpc.jsonrpc import HTTPClient, RPCError

    from test_light import LightChain, _client

    chain = LightChain(12)
    box = {}

    async def go():
        client = _client(chain, witnesses=[chain.provider(),
                                           chain.provider()])
        await client.initialize()
        pool = ServingPool(client, workers=1,
                           config=LightConfig(flush_ms=1.0))
        pool.plane.collector.device_threshold = 10**9
        TRACER.clear()
        # (listen, not start: the shapes' load is the next test's)
        (port,) = await pool.listen("127.0.0.1")
        rpc = HTTPClient("127.0.0.1", port)
        try:
            await rpc.call("commit")               # latest: 1 -> 12
            await rpc.call("commit", height=5)     # between: 1 -> 5
            await rpc.call("header", height=5)     # the cache's
            with pytest.raises(RPCError):
                await rpc.call("commit", height=99)
        finally:
            box["plane"] = pool.plane
            pool.close()

    run(go())
    return [r for r in TRACER.snapshot() if r[0] in LIGHT_KINDS], \
        box["plane"]


@pytest.mark.parametrize("kind", sorted(LIGHT_KINDS))
def test_light_request_path_spans_at_their_sites(kind):
    """Every per-request site of the light proxy's path leaves ONE
    folded entry a run (n, busy_ns and its sums), however many requests
    ran: the ring holds 16,384 and a proxy answers hundreds a second."""
    recs, plane = _light_requests()
    mine = [r for r in recs if r[0] == kind]
    assert len(mine) == 1, [r[0] for r in recs]
    attrs = mine[0][6]
    want = {
        # four routes entered; one failed; 5 came from the LRU
        tracing.LIGHT_REQUEST: dict(n=4, hits=1, coalesced=0, misses=3,
                                    failed=1),
        # primary: 12, 5, 99 (not found); witnesses: two a verified block
        tracing.LIGHT_FETCH: dict(n=3 + 2 * 2, witness=4),
        # 1 -> 12 and 1 -> 5: a trusting and an own plan each
        tracing.LIGHT_PLAN: dict(n=4, trusting=2),
        tracing.LIGHT_STEP: dict(n=2, adjacent=0, pivots=0,
                                 gap=11 + 4),
        tracing.LIGHT_STORE_SAVE: dict(n=2),
        tracing.LIGHT_DETECT: dict(n=2),
    }[kind]
    for key, value in want.items():
        assert attrs.get(key, 1 if key == "n" else 0) == value, attrs
    assert set(LIGHT_KINDS[kind]) <= set(attrs) | {"n", "busy_ns"} \
        or attrs.get("n", 1) == 1
    if kind == tracing.LIGHT_PLAN:
        assert attrs["lanes"] >= 4 * 2      # >1/3 and >2/3 of 4 keys
    assert plane.steps == 2 and plane.hash_walks == 0
    assert mine[0][2] == 0                  # a root: no request's child


def test_light_load_programs_span_and_the_ports_after_it(monkeypatch):
    """ServingPool.start loads the plane's launch shapes (span
    light.load_programs {programs, seconds, lanes}) BEFORE a port is
    open; a plane whose shapes were loaded already leaves no span."""
    from tendermint_tpu.config import LightConfig
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.light import ServingPool

    from test_light import LightChain, _client

    order = []

    def fake_load(shapes):
        order.append(("load", shapes.lanes, shapes.blocks))
        return 2 if len(order) == 1 else 0

    monkeypatch.setattr(cbatch, "load_ed25519_programs", fake_load)
    chain = LightChain(3)

    async def go():
        for _ in range(2):
            pool = ServingPool(_client(chain), workers=2,
                               config=LightConfig(batch_max=512))
            real = pool.listen

            async def listen(host, ports, real=real):
                order.append(("listen", len(ports)))
                return await real(host, ports)

            pool.listen = listen
            try:
                ports = await pool.start("127.0.0.1")
                assert len(ports) == 2 and all(ports)
            finally:
                pool.close()

    TRACER.clear()
    run(go())
    assert order == [("load", 512, 2), ("listen", 2)] * 2
    (span,) = [r for r in TRACER.snapshot()
               if r[0] == tracing.LIGHT_LOAD_PROGRAMS]
    assert span[6]["programs"] == 2 and span[6]["lanes"] == 512
    assert span[6]["seconds"] >= 0


# ------------------------------------------------------------ device names


def test_jitted_program_names_are_pinned():
    """benchmark/layer_metrics/trace_module.py finds the programs in a
    profiler trace by these names (`jit_skernel`, `jit_kernel`)."""
    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv

    assert ex._skernel().__name__ == "skernel"
    assert ex._skernel_sharded().__name__ == "skernel"
    assert ex._xkernel().__name__ == "kernel"
    assert tv._kernel().__name__ == "kernel"
    # the sr25519 program has a name of its own: `jit_kernel` is the
    # general ed25519 program's, and kernel_ms.* reads that one
    from tendermint_tpu.crypto.tpu import sr_verify

    assert sr_verify._kernel().__name__ == "sr25519_kernel"
    assert sr_verify.PHASES == ("sr25519.merlin", "sr25519.decode",
                                "sr25519.table", "sr25519.msm",
                                "sr25519.compare")


def test_assemble_is_traced_under_its_phase():
    import jax

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv

    n = 8
    jaxpr = jax.make_jaxpr(
        lambda *a: ex.assemble_core()(*a, 192))(
        np.zeros((32, 128), np.uint8), np.zeros(32, np.int32),
        np.zeros((32, 64), np.uint8), np.zeros(32, np.int32),
        np.zeros((n, 24), np.uint8), np.zeros(n, np.int32),
        np.zeros(n, np.int32), np.zeros(n, np.int32))
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert stacks == {tv.PHASE_ASSEMBLE}
    assert set(tv.PHASES) == {
        "ed25519.assemble", "ed25519.gather", "ed25519.sha512",
        "ed25519.decompress", "ed25519.msm", "ed25519.compare"}


def test_sr25519_kernel_is_traced_under_its_phases():
    import jax

    from helpers import sr_kernel_args

    from tendermint_tpu.crypto.tpu import sr_verify

    jaxpr = jax.make_jaxpr(sr_verify._kernel())(**sr_kernel_args(8))
    (call,) = jaxpr.jaxpr.eqns
    assert call.params["name"] == "sr25519_kernel"
    scopes = {str(e.source_info.name_stack).split("/")[0]
              for e in call.params["jaxpr"].jaxpr.eqns}
    assert scopes == set(sr_verify.PHASES)   # nothing outside a phase


def test_phase_of_instructions_reads_optimized_hlo():
    from tendermint_tpu.crypto.tpu import verify as tv

    hlo = '''
%fused_computation.7 (p: s32[8]) -> s32[8] {
  %select.3 = s32[8]{0} select(%a, %b, %c), metadata={op_name="jit(skernel)/ed25519.assemble/select_n" stack_frame_id=4}
}
ENTRY %main {
  %fusion.7 = s32[1966080]{0:T(1024)} fusion(%p.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(skernel)/ed25519.assemble/select_n" stack_frame_id=4}
  %fusion.5 = s32[706560,128]{1,0} fusion(%atab, %bitcast.2), kind=kCustom, calls=%fc.5, metadata={op_name="jit(skernel)/ed25519.gather/gather"}
  %while.1 = (s32[], s32[22,8]) while(%tuple.3), condition=%cond, body=%body, metadata={op_name="jit(skernel)/ed25519.msm/while"}
  %mul.9 = s32[22,8]{1,0} multiply(%x, %y), metadata={op_name="jit(skernel)/ed25519.msm/while/body/ed25519.decompress/mul"}
  %copy.139 = s32[69,88,8]{2,1,0} copy(%bitcast.77)
  %add.1 = s32[8]{0} add(%x, %y), metadata={op_name="jit(skernel)/not_ed25519.msm/add"}
  ROOT %and.4 = pred[8]{0} and(%l, %r), metadata={op_name="jit(skernel)/ed25519.compare/and"}
}
'''
    assert tv.phase_of_instructions(hlo) == {
        "select.3": "ed25519.assemble",
        "fusion.7": "ed25519.assemble",
        "fusion.5": "ed25519.gather",
        "while.1": "ed25519.msm",
        "mul.9": "ed25519.decompress",     # the innermost scope
        "and.4": "ed25519.compare",
    }


# ----------------------------------------------------------- /debug/profile


def test_debug_profile_endpoint(tmp_path, monkeypatch):
    """On the CPU backend: the profiler runs, the clock-sync stamp and
    the interval's spans come back, a second session is refused."""
    import glob
    import os
    import time

    from tendermint_tpu.libs import debugsrv

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    async def get(port, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        raw = await reader.read()
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"200 OK" in head and b"application/json" in head
        return json.loads(body)

    async def go():
        srv = debugsrv.DebugServer()
        port = await srv.start()
        try:
            first = asyncio.ensure_future(
                get(port, "/debug/profile?seconds=0.4"))
            while not debugsrv._device_profile_running:
                await asyncio.sleep(0.01)
            second = await get(port, "/debug/profile?seconds=0.1")
            while not first.done():   # the node's work goes on meanwhile
                with TRACER.span(tracing.CRYPTO_PACK, lanes=1):
                    await asyncio.sleep(0.01)
            return await first, second
        finally:
            srv.close()

    before = time.perf_counter_ns()
    TRACER.clear()
    first, second = run(go())
    assert second == {"error": "a device profile is already running"}
    assert before < first["sync_ns"] < time.perf_counter_ns()
    assert 0.4 <= first["seconds"] < 5.0
    assert os.path.dirname(first["trace_dir"]) == str(tmp_path)
    assert glob.glob(os.path.join(first["trace_dir"], "plugins", "profile",
                                  "*", "*.xplane.pb"))
    assert first["spans"] and {s[0] for s in first["spans"]} == {
        tracing.CRYPTO_PACK}
    assert all(s[1] < first["sync_ns"] + first["seconds"] * 1e9
               and s[1] + s[2] > first["sync_ns"] for s in first["spans"])
    assert first["spans_dropped"] == 0
    # the cap holds whatever is asked for
    assert debugsrv._parse_seconds(
        "3600", 1.0, cap=debugsrv.DEVICE_PROFILE_CAP_S) == 10.0
    assert debugsrv._device_profile_running is False
