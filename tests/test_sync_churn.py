"""Fast sync through validator-set changes: the verify-apply loop the
reactor and the benchmark share (`verify_ahead.sync_window`) over a
128-validator chain whose set moves three times, against a sequential
host oracle under a plain model of the set
(`benchmark/reference/valset_model.py`); the model against
`update_with_change_set` + `update_state`; the weighted BFT-time
median; the app's validator journal; the reactor's bans.

No kernel is compiled: `HostTables` stands where `ExpandedKeys`
stands, so `get_expanded`'s cache, its builds and their spans are the
real ones and the lanes are verified on the host under the key the
tables hold at each index."""

from __future__ import annotations

import asyncio
import random
import threading
from collections import OrderedDict

import numpy as np
import pytest

from benchmark.reference import valset_model as vm
from tendermint_tpu.abci import types as abci_t
from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
from tendermint_tpu.blockchain import verify_ahead as va
from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.tracing import TRACER
from tendermint_tpu.state import make_genesis_state, median_time
from tendermint_tpu.state.execution import (
    BlockExecutor, update_state, validator_updates_from_abci)
from tendermint_tpu.state.store import Store
from tendermint_tpu.store import BlockStore
from tendermint_tpu.types.block import (
    BlockID, BlockIDFlag, Commit, CommitSig)
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote, VoteType

from helpers import CHAIN_ID, GENESIS_TIME, deterministic_pv

N = 128
UPDATES = {10: "membership", 20: "reweight", 30: "membership"}
SWAP, REWEIGHTED = 3, 8


val_tx = vm.val_tx


class Chain:
    """A chain made with the program's own block builder and the
    persistent kvstore app; every validator signs every commit, each
    at a time of its own, so a block's time is a weighted median."""

    def __init__(self, n_blocks: int, updates: dict = UPDATES,
                 bad: tuple[int, str] | None = None, seed: int = 7):
        rng = random.Random(seed)
        self.pvs = {}          # public key -> MockPV
        genesis = {}           # public key -> power at height 1
        for i in range(N):
            pv = deterministic_pv(i)
            pk = pv.get_pub_key().bytes()
            self.pvs[pk] = pv
            genesis[pk] = rng.randint(950, 1050)
        self.model = vm.ValsetModel(genesis)
        self.gdoc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=GENESIS_TIME,
            validators=[GenesisValidator(Ed25519PubKey(pk), p)
                        for pk, p in genesis.items()])
        self.gdoc.validate_and_complete()
        state = make_genesis_state(self.gdoc)
        app = PersistentKVStoreApp(MemDB())
        app.init_chain(abci_t.RequestInitChain(validators=[
            abci_t.ValidatorUpdate("ed25519", pk, p)
            for pk, p in genesis.items()]))
        current = dict(genesis)
        self.blocks, self.bids, self.bad_index = [], [], None
        last_commit, joined = None, 0
        for h in range(1, n_blocks + 1):
            txs = [b"k%d=v%d" % (h, h)]
            kind = updates.get(h)
            if kind == "membership":
                leavers = rng.sample(sorted(current), SWAP)
                for pk in leavers:
                    pv = deterministic_pv(N + joined)
                    joined += 1
                    new = pv.get_pub_key().bytes()
                    self.pvs[new] = pv
                    txs += [val_tx(pk, 0), val_tx(new, current[pk])]
                    current[new] = current.pop(pk)
            elif kind == "reweight":
                for pk in rng.sample(sorted(current), REWEIGHTED):
                    current[pk] = rng.choice(
                        [p for p in range(950, 1051) if p != current[pk]])
                    txs.append(val_tx(pk, current[pk]))
            when = state.last_block_time if h == 1 else median_time(
                last_commit, state.last_validators)
            block = state.make_block(
                h, txs, last_commit, [],
                state.validators.get_proposer().address, when)
            bid = BlockID(block.hash(), block.make_part_set().header())
            self.blocks.append(block)
            self.bids.append(bid)
            app.begin_block(abci_t.RequestBeginBlock())
            delivered = [app.deliver_tx(abci_t.RequestDeliverTx(tx))
                         for tx in txs]
            end = app.end_block(abci_t.RequestEndBlock(h))
            signers = state.validators
            state = update_state(
                state, bid, block,
                {"deliver_txs": delivered, "end_block": end},
                validator_updates_from_abci(end.validator_updates))
            state.app_hash = app.commit(abci_t.RequestCommit()).data
            self.model.deliver_block(h, txs)
            last_commit = self._commit(signers, h, bid, when)
            if bad is not None and h == bad[0]:
                self.bad_index = self._plant(last_commit, bad[1])

    def _commit(self, signers: ValidatorSet, h, bid, when) -> Commit:
        sigs = []
        for i, val in enumerate(signers.validators):
            t = when + 1_000_000_000 + 1_000 * i
            vote = Vote(type=VoteType.PRECOMMIT, height=h, round=0,
                        block_id=bid, timestamp=t,
                        validator_address=val.address, validator_index=i)
            self.pvs[val.pub_key.bytes()].sign_vote(CHAIN_ID, vote)
            sigs.append(CommitSig(BlockIDFlag.COMMIT, val.address, t,
                                  vote.signature))
        return Commit(h, 0, bid, sigs)

    def _plant(self, commit: Commit, who: str) -> int:
        """Corrupt the signature of the first validator (in the order
        in force) that joined by update (`joiner`) or not."""
        genesis_keys = dict(self.model.in_force(1))
        for i, (pk, _) in enumerate(self.model.in_force(commit.height)):
            if (pk not in genesis_keys) == (who == "joiner"):
                sig = bytearray(commit.signatures[i].signature)
                sig[40] ^= 1
                commit.signatures[i].signature = bytes(sig)
                return i
        raise AssertionError("no such validator")


class HostTables:
    """Where ExpandedKeys stands: the set's keys in table order, each
    lane verified on the host under the key at its index."""

    built: list[bytes] = []
    sharded = False

    def __init__(self, pubkeys):
        from tendermint_tpu.crypto.tpu import expanded

        self.pubkeys = tuple(pubkeys)
        self.tables = np.zeros((len(self.pubkeys), 4), np.int32)
        HostTables.built.append(expanded.key_digest(list(pubkeys)))

    def _lanes(self, indices, msg_of, sigs):
        return np.array([
            Ed25519PubKey(self.pubkeys[i]).verify_signature(
                msg_of(j), sigs[j]) for j, i in enumerate(indices)], bool)

    def verify_structured(self, indices, sbatch, sigs):
        return self._lanes(indices, sbatch.host_assemble, sigs)

    def verify(self, indices, msgs, sigs):
        return self._lanes(indices, msgs.__getitem__, sigs)


@pytest.fixture
def host_tables(monkeypatch):
    import tendermint_tpu.types.validator_set as vs_mod
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.tpu import expanded

    cbatch.reset_breakers()
    monkeypatch.setattr(expanded, "ExpandedKeys", HostTables)
    monkeypatch.setattr(expanded, "_CACHE", OrderedDict())
    monkeypatch.setattr(expanded, "max_keys", lambda: 1 << 20)
    monkeypatch.setattr(vs_mod, "_EXPAND_MIN", 64)
    HostTables.built = []
    yield HostTables
    for t in threading.enumerate():
        if t.name == "expanded-warm":
            t.join()


def oracle(chain: Chain) -> tuple[int, int | None]:
    """(blocks a sequential sync applies, height it refuses): block h
    checked with block h+1's LastCommit, one signature at a time on the
    host, under the model's set for h, as VerifyCommitLight reads it."""
    for h in range(1, len(chain.blocks)):
        commit = chain.blocks[h].last_commit
        in_force = chain.model.in_force(h)
        need = 2 * sum(p for _, p in in_force)
        tally = 0
        for i, (pk, power) in enumerate(in_force):
            cs = commit.signatures[i]
            if not Ed25519PubKey(pk).verify_signature(
                    commit.vote_sign_bytes(CHAIN_ID, i), cs.signature):
                return h - 1, h
            tally += power
            if 3 * tally > need:
                break
    return len(chain.blocks) - 1, None


async def sync(chain: Chain, log: list | None = None):
    """The chain through sync_window into fresh MemDB stores, as the
    reactor drives it. Returns (state, state store, applied, refusal)."""
    state = make_genesis_state(chain.gdoc)
    store = Store(MemDB())
    store.save(state)
    block_store = BlockStore(MemDB())
    app = PersistentKVStoreApp(MemDB())
    app.init_chain(abci_t.RequestInitChain(validators=[
        abci_t.ValidatorUpdate("ed25519", v.pub_key.bytes(),
                               v.voting_power)
        for v in state.validators.validators]))
    client = LocalClient(app)
    await client.start()
    executor = BlockExecutor(store, client)
    pipeline = va.WindowPipeline()
    pos, total, refused = 0, 0, None

    def peek(k):
        return chain.blocks[pos:pos + k]

    def after_block(new_state, block):
        nonlocal pos
        pos += 1
        if log is not None:
            log.append((block.header.height, new_state.validators))

    try:
        while refused is None:
            window = peek(va.BATCH_WINDOW + 1)
            if len(window) < 2:
                break
            state, applied, refused = await va.sync_window(
                pipeline, state, window, peek, block_store, executor,
                lambda block: None, after_block)
            total += applied
    finally:
        pf = pipeline._prefetch
        if pf is not None:
            await asyncio.wait([pf[1]])
        await client.stop()
    assert block_store.height == state.last_block_height == total
    return state, store, total, refused


@pytest.fixture(scope="module")
def sound_chain():
    return Chain(40)


def test_sync_window_applies_what_the_sequential_oracle_applies(
        host_tables, sound_chain, monkeypatch):
    chain = sound_chain
    verified = []    # (set hash, heights) of every window job
    real = va._batch_verify_window

    def logged(vals, chain_id, items):
        verified.append((vals.hash(), [h for _, h, _ in items]))
        return real(vals, chain_id, items)

    monkeypatch.setattr(va, "_batch_verify_window", logged)
    TRACER.clear()
    applied_log: list = []
    state, store, applied, refused = asyncio.run(sync(chain, applied_log))
    assert (applied, refused) == (39, None) == oracle(chain)

    # every applied block was verified under the set the model has in
    # force at its height, and by no stale window alone
    for h, _ in applied_log:
        want = vm.validators_hash(chain.model.in_force(h))
        assert any(h in heights and vh == want
                   for vh, heights in verified), h
    # the set in force after each block is the model's: keys, powers,
    # order and hash; and the state store holds it for that height
    for h, vals in applied_log:
        want = chain.model.in_force(h + 1)
        assert [(v.pub_key.bytes(), v.voting_power)
                for v in vals.validators] == want
        assert vals.hash() == vm.validators_hash(want)
        assert store.load_validators(h + 1).hash() == vals.hash()

    recs = TRACER.snapshot()
    # one table build a digest, three generations through a cache of two
    builds = [r for r in recs if r[0] == tracing.CRYPTO_TABLE_BUILD]
    assert len(builds) == len(host_tables.built) == 4
    assert len(set(host_tables.built)) == 4
    assert [b[6]["keys"] for b in builds] == [N] * 4
    assert sorted(b[6]["thread"] for b in builds) == [
        "inline", "warm", "warm", "warm"]
    assert sum(b[6]["evicted"] for b in builds) == 2
    # each change cut a window: the blocks past it were verified under
    # the old set and thrown away
    cuts = [r[6] for r in recs if r[0] == tracing.SYNC_WINDOW_CUT]
    assert [(c["height"], c["applied"] < c["verified"]) for c in cuts] == [
        (11, True), (21, True), (31, True)]


@pytest.mark.parametrize("height,who", [
    (8, "genesis"),      # under the genesis set
    (14, "joiner"),      # signed by a validator that joined at 10
    (25, "joiner"),      # after the re-weighting reordered the set
    (33, "joiner"),      # under the fourth set
])
def test_sync_window_refuses_the_planted_commit_and_names_its_index(
        host_tables, height, who):
    chain = Chain(40, bad=(height, who))
    state, _, applied, refused = asyncio.run(sync(chain))
    assert (applied, refused.height) == (height - 1, height) == \
        oracle(chain)
    assert refused.index == (applied - _window_start(applied))
    # the full check names the signature by its index in the order in
    # force at that height (the model's), not in any earlier order
    commit = chain.blocks[height].last_commit
    with pytest.raises(Exception) as e:
        state.validators.verify_commit(
            CHAIN_ID, chain.bids[height - 1], height, commit)
    assert str(e.value) == \
        f"invalid signature(s) at index(es) [{chain.bad_index}]"
    key, _ = chain.model.in_force(height)[chain.bad_index]
    assert state.validators.validators[chain.bad_index].pub_key.bytes() \
        == key


def _window_start(applied: int) -> int:
    """Blocks applied before the window that holds block applied+1:
    windows start at 0 and after each cut (11, 21, 31)."""
    return max(s for s in (0, 11, 21, 31) if s <= applied)


def test_a_stale_window_verified_ahead_is_discarded_not_trusted(
        host_tables, sound_chain):
    """The window verified ahead under the old set covers blocks signed
    by the new one: its verdicts are refusals, and a sync that trusted
    them would stop at the change."""
    chain = sound_chain
    state = make_genesis_state(chain.gdoc)
    stale = va._batch_verify_window(
        state.validators, CHAIN_ID, va.window_items(chain.blocks[11:28])[0])
    assert any(err is not None for err in stale)
    pipeline_hits = []
    real = va.WindowPipeline.verdicts

    async def counted(self, vals, chain_id, blocks):
        before = self.prefetch_hits
        out = await real(self, vals, chain_id, blocks)
        pipeline_hits.append(self.prefetch_hits - before)
        return out

    va.WindowPipeline.verdicts = counted
    try:
        _, _, applied, refused = asyncio.run(sync(chain))
    finally:
        va.WindowPipeline.verdicts = real
    assert (applied, refused) == (39, None)
    # windows after a cut start where no prefetch began: none is served
    # from the one verified ahead
    assert pipeline_hits == [0, 0, 0, 0]


# ------------------------------------------------- the model of the set


def _validator(pk: bytes, power: int) -> Validator:
    return Validator.new(Ed25519PubKey(pk), power)


@pytest.mark.parametrize("kind", ["join", "leave", "reweight", "all"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_valset_model_equals_update_with_change_set(kind, seed):
    rng = random.Random(seed * 1000 + len(kind))
    keys = [deterministic_pv(i).get_pub_key().bytes() for i in range(60)]
    genesis = {pk: rng.randint(950, 1050) for pk in keys[:40]}
    model = vm.ValsetModel(genesis)
    vals = ValidatorSet([_validator(pk, p) for pk, p in genesis.items()])
    assert [(v.pub_key.bytes(), v.voting_power)
            for v in vals.validators] == model.in_force(1)
    current, spare = dict(genesis), keys[40:]
    for h in range(1, 6):
        change = []
        if kind in ("join", "all"):
            change += [(spare.pop(), rng.randint(950, 1050))
                       for _ in range(3)]
        if kind in ("leave", "all"):
            change += [(pk, 0) for pk in rng.sample(sorted(current), 3)]
        if kind in ("reweight", "all"):
            free = sorted(set(current) - {pk for pk, _ in change})
            change += [(pk, rng.randint(950, 1050))
                       for pk in rng.sample(free, 5)]
        rng.shuffle(change)
        for pk, power in change:
            if power:
                current[pk] = power
            else:
                del current[pk]
        model.deliver_block(h, [val_tx(pk, p) for pk, p in change])
        vals = vals.copy()
        vals.update_with_change_set(
            [_validator(pk, p) for pk, p in change])
        want = model.in_force(h + 2)
        assert dict(want) == current
        assert [(v.pub_key.bytes(), v.voting_power)
                for v in vals.validators] == want
        assert vals.hash() == vm.validators_hash(want)
        # and the heights between: the change of block h is not in
        # force at h + 1
        assert dict(model.in_force(h + 1)) != current or not change


def test_valset_model_control_never_applies_a_change():
    keys = [deterministic_pv(i).get_pub_key().bytes() for i in range(5)]
    genesis = {pk: 10 for pk in keys[:4]}
    idle = vm.ValsetModel(genesis, apply_updates=False)
    idle.deliver_block(1, [val_tx(keys[4], 7), val_tx(keys[0], 0)])
    assert dict(idle.in_force(3)) == genesis
    kv = vm.PersistentKVStoreModel()
    kv.deliver(val_tx(keys[4], 7))
    kv.deliver(b"a=b")
    assert kv.size == 1 and kv.values == {b"a": b"b"}


# ------------------------------------------------------------- BFT time


def _commit_at(times_and_powers):
    vals = ValidatorSet([
        _validator(deterministic_pv(i).get_pub_key().bytes(), p)
        for i, (_, p) in enumerate(times_and_powers)])
    by_key = {deterministic_pv(i).get_pub_key().address(): t
              for i, (t, _) in enumerate(times_and_powers)}
    sigs = [CommitSig(BlockIDFlag.COMMIT, v.address, by_key[v.address],
                      b"\1" * 64) for v in vals.validators]
    bid = BlockID(b"\2" * 32, None)
    return Commit(1, 0, bid, sigs), vals


@pytest.mark.parametrize("times_and_powers,expected", [
    # the reference's own test (types/time/time_test.go)
    ([(1, 33), (6, 40), (11, 27)], 6),
    # an odd total, a run of votes summing to exactly half rounded
    # down: the reference names THAT vote's time
    ([(1, 1), (2, 1), (3, 1)], 1),
    ([(10, 3), (20, 2), (30, 6)], 20),
    ([(5, 7)], 5),
])
def test_median_time_is_the_references_weighted_median(
        times_and_powers, expected):
    commit, vals = _commit_at(times_and_powers)
    assert median_time(commit, vals) == expected
    assert vm.weighted_median(times_and_powers) == expected


@pytest.mark.parametrize("seed", range(4))
def test_median_time_equals_the_model_on_weighted_sets(seed):
    rng = random.Random(seed)
    pairs = [(1_700_000_000_000_000_000 + rng.randrange(10**9),
              rng.randint(1, 50)) for _ in range(rng.randint(1, 40))]
    commit, vals = _commit_at(pairs)
    assert median_time(commit, vals) == vm.weighted_median(pairs)


# ----------------------------------------------- the app's set journal


def test_persistent_kvstore_replays_a_half_block_of_leavers_and_joiners():
    """10 leave and 10 join in one block; the block is delivered again
    before any Commit (a crash replay): the journal puts the set back
    first, so the second delivery ends where one delivery ends."""
    keys = [deterministic_pv(i).get_pub_key().bytes() for i in range(50)]
    app = PersistentKVStoreApp(MemDB())
    app.init_chain(abci_t.RequestInitChain(validators=[
        abci_t.ValidatorUpdate("ed25519", pk, 100 + i)
        for i, pk in enumerate(keys[:40])]))
    before = dict(app.validators)
    txs = [val_tx(pk, 0) for pk in keys[:10]] + \
        [val_tx(pk, 7) for pk in keys[40:]]

    def deliver():
        app.begin_block(abci_t.RequestBeginBlock())
        assert app.validators == before
        for tx in txs:
            assert app.deliver_tx(abci_t.RequestDeliverTx(tx)).code == 0
        return app.end_block(abci_t.RequestEndBlock(1))

    deliver()
    end = deliver()
    assert [(u.pub_key, u.power) for u in end.validator_updates] == \
        [(pk, 0) for pk in keys[:10]] + [(pk, 7) for pk in keys[40:]]
    app.commit(abci_t.RequestCommit())
    want = {pk.hex(): 100 + i for i, pk in enumerate(keys[:40])
            if i >= 10}
    want.update({pk.hex(): 7 for pk in keys[40:]})
    assert app.validators == want and app.size == 0
    reopened = PersistentKVStoreApp(app.db.base)
    assert reopened.validators == want


# ------------------------------------------------------ the reactor's bans


def test_reactor_bans_both_peers_of_a_refused_block():
    pytest.importorskip("cryptography")
    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    from helpers import (
        commit_for, make_genesis_state_and_pvs, next_block)

    class Switch:
        def __init__(self):
            self.peers = {"pa": "peer-a", "pb": "peer-b"}
            self.errors, self.observed = [], []
            self.reporter = self

        def observe(self, peer_id, bad):
            self.observed.append((peer_id, bad))

        def _on_peer_error(self, peer, err):
            self.errors.append((peer, str(err)))

    async def go():
        state, pvs = make_genesis_state_and_pvs(4)
        app = PersistentKVStoreApp(MemDB())
        client = LocalClient(app)
        await client.start()
        # the chain, made by applying it
        store = Store(MemDB())
        store.save(state)
        executor = BlockExecutor(store, client)
        blocks, last_commit, st = [], None, state
        for _ in range(5):
            block, bid = next_block(st, pvs, last_commit, [b"a=b"])
            last_commit = commit_for(st, pvs, block, bid)
            st, _ = await executor.apply_block(st, bid, block)
            blocks.append(block)
        # block 4 carries the commit of block 3, with one signature bad
        blocks[3].last_commit.signatures[1].signature = b"\0" * 64

        app2 = PersistentKVStoreApp(MemDB())
        client2 = LocalClient(app2)
        await client2.start()
        store2 = Store(MemDB())
        store2.save(state)
        reactor = BlockchainReactor(
            state, BlockExecutor(store2, client2), BlockStore(MemDB()),
            fast_sync=True)
        reactor.switch = sw = Switch()
        pool = reactor.pool
        pool.set_peer_range("pa", 1, 5)
        pool.set_peer_range("pb", 1, 5)
        served = dict((h, p) for p, h in pool.make_next_requests(now=0.0))
        for block in blocks:
            h = block.header.height
            assert pool.add_block(served[h], block, 100)
        try:
            assert await reactor._try_sync() is True
        finally:
            pf = reactor.pipeline._prefetch
            if pf is not None:
                await asyncio.wait([pf[1]])
            await client.stop()
            await client2.stop()
        return reactor, sw, served

    reactor, sw, served = asyncio.run(go())
    # blocks 1 and 2 applied, 3 refused: the peers that served block 3
    # and block 4 (whose LastCommit condemned it) are both gone
    assert reactor.blocks_synced == 2
    assert reactor.state.last_block_height == 2
    assert reactor.block_store.height == 2 and reactor.pool.height == 3
    culprits = {served[3], served[4]}
    assert {p for p, _ in sw.observed} == culprits
    assert {peer for peer, _ in sw.errors} == {
        sw.peers[p] for p in culprits}
    assert all("bad block" in msg for _, msg in sw.errors)
    assert not culprits & set(reactor.pool.peers)
