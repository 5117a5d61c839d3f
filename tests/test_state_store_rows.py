"""The state store's validator-set rows (state/store.py): a set's
membership is written where it changes, every other row and the state
row rest on it, and whatever is read back is the set that was written."""

import asyncio
import json

import pytest

from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.state import make_genesis_state
from tendermint_tpu.state import store as store_mod
from tendermint_tpu.state.execution import (
    BlockExecutor, build_last_commit_info,
)
from tendermint_tpu.state.store import Store
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

from helpers import (
    commit_for, deterministic_pv, make_genesis, next_block,
)

SETS = ("validators", "next_validators", "last_validators")


def facts(vs: ValidatorSet):
    """Everything a stored set has to give back."""
    return ([(v.address, v.pub_key.type_name, v.pub_key.bytes(),
              v.voting_power, v.proposer_priority)
             for v in vs.validators],
            vs.proposer.address if vs.proposer is not None else None,
            vs.hash())


def val_tx(pv, power: int) -> bytes:
    return b"val:%s!%d" % (pv.get_pub_key().bytes().hex().encode(), power)


def apply_chain(n_blocks: int, txs_at: dict[int, list[bytes]],
                extra_pvs=(), store: Store | None = None):
    """A 4-validator chain applied through BlockExecutor onto
    PersistentKVStoreApp. Returns (store, states, blocks): states[h]
    is the state after block h (states[0] the genesis state)."""
    async def go():
        gdoc, pvs = make_genesis(4)
        pvs = pvs + list(extra_pvs)
        state = make_genesis_state(gdoc)
        st = store or Store(MemDB())
        st.save(state)
        client = LocalClient(PersistentKVStoreApp(MemDB()))
        await client.start()
        executor = BlockExecutor(st, client)
        states, blocks, last_commit = [state], [None], None
        for h in range(1, n_blocks + 1):
            block, bid = next_block(state, pvs, last_commit,
                                    txs_at.get(h, []))
            seen = commit_for(state, pvs, block, bid)
            state, _ = await executor.apply_block(state, bid, block)
            states.append(state)
            blocks.append(block)
            last_commit = seen
        await client.stop()
        return st, states, blocks

    return asyncio.run(go())


def full_rows(store: Store, heights) -> list[int]:
    out = []
    for h in heights:
        raw = store.db.get(store_mod._valset_key(h))
        if raw is not None and store_mod._read_record(raw)[0] == 0:
            out.append(h)
    return out


JOINER = deterministic_pv(40)


@pytest.fixture(scope="module")
def chain():
    """Five still blocks, a joiner and a re-weighting that reorders in
    block 6 (in force at 8), six more blocks."""
    reweighted = deterministic_pv(2)
    return apply_chain(12, {6: [val_tx(JOINER, 7), val_tx(reweighted, 25)]},
                       extra_pvs=[JOINER])


def test_load_returns_the_three_sets_that_were_saved(chain):
    store, states, _ = chain
    loaded = store.load()
    live = states[-1]
    assert loaded.last_block_height == 12
    assert loaded.last_height_validators_changed == 8 \
        == live.last_height_validators_changed
    assert loaded.app_hash == live.app_hash
    assert loaded.last_block_id == live.last_block_id
    for name in SETS:
        assert facts(getattr(loaded, name)) == facts(getattr(live, name))
    # one membership by now, three columns of priorities
    assert len({tuple(v[4] for v in facts(getattr(live, n))[0])
                for n in SETS}) == 3
    # objects of its own: moving one set moves no other
    loaded.next_validators.increment_proposer_priority(1)
    assert facts(loaded.validators) == facts(live.validators)


def test_load_right_after_an_update_block(chain):
    """State 6 holds three sets of which next_validators alone has the
    joiner and the new order: saved again into an empty store it rests
    on nothing and still loads whole."""
    _, states, _ = chain
    live = states[6]
    assert len(live.next_validators) == 5 and len(live.validators) == 4
    store = Store(MemDB())
    store.save(live)
    loaded = store.load()
    for name in SETS:
        assert facts(getattr(loaded, name)) == facts(getattr(live, name))


def test_every_height_reads_back_the_set_that_was_in_force(chain):
    store, states, _ = chain
    for h in range(1, 13):
        # the state before block h holds the set of h as `validators`
        assert facts(store.load_validators(h)) == \
            facts(states[h - 1].validators), h
    assert facts(store.load_validators(13)) == facts(states[12].validators)
    assert facts(store.load_validators(14)) == \
        facts(states[12].next_validators)
    assert store.load_validators(15) is None
    # genesis wrote the rows of heights 1 and 2, block 6 the row of 8
    assert full_rows(store, range(1, 16)) == [1, 2, 8]
    order = [v.address for v in store.load_validators(8).validators]
    assert order != [v.address for v in store.load_validators(7).validators]
    assert JOINER.get_pub_key().address() in order


def test_prune_keeps_the_row_a_kept_height_rests_on(chain):
    store, states, _ = chain
    pruned = Store(MemDB())
    for h in range(1, 15):
        key = store_mod._valset_key(h)
        pruned.db.set(key, store.db.get(key))
    pruned.db.set(b"stateKey", store.db.get(b"stateKey"))
    def rows():
        return [h for h in range(1, 15)
                if pruned.db.get(store_mod._valset_key(h)) is not None]

    pruned.prune_states(1, 5)
    # heights 5 to 7 rest on the row of 2
    assert rows() == [2] + list(range(5, 15))
    for h in (5, 7, 8):
        assert facts(pruned.load_validators(h)) == \
            facts(states[h - 1].validators)
    pruned.prune_states(1, 11)
    # height 11 and the state's three sets rest on the row of 8; the
    # row of 2 has nothing left to carry
    assert rows() == [8, 11, 12, 13, 14]
    for h in (11, 12, 13):
        assert facts(pruned.load_validators(h)) == \
            facts(states[h - 1].validators)
    for name in SETS:
        assert facts(getattr(pruned.load(), name)) == \
            facts(getattr(states[12], name))
    assert pruned.load_validators(10) is None


def parent_state_bytes(state) -> bytes:
    """`_state_bytes` as the commit before set records wrote it."""
    bid = state.last_block_id
    psh = bid.part_set_header
    return json.dumps({
        "chain_id": state.chain_id,
        "initial_height": state.initial_height,
        "last_block_height": state.last_block_height,
        "last_block_id": {
            "hash": bid.hash.hex(),
            "psh_total": psh.total if psh else 0,
            "psh_hash": psh.hash.hex() if psh else "",
        },
        "last_block_time": state.last_block_time,
        "validators": store_mod._valset_to_json(state.validators),
        "next_validators": store_mod._valset_to_json(state.next_validators),
        "last_validators": store_mod._valset_to_json(state.last_validators),
        "last_height_validators_changed":
            state.last_height_validators_changed,
        "consensus_params": state.consensus_params.to_json(),
        "last_height_consensus_params_changed":
            state.last_height_consensus_params_changed,
        "last_results_hash": state.last_results_hash.hex(),
        "app_hash": state.app_hash.hex(),
        "app_version": state.app_version,
    }).encode()


def test_rows_of_the_json_era_load_and_carry_the_rows_written_next(chain):
    _, states, _ = chain
    store = Store(MemDB())
    for h in range(1, 10):   # what the parent left behind after block 7
        store.db.set(store_mod._valset_key(h), json.dumps(
            store_mod._valset_to_json(states[h - 1].validators)).encode())
    store.db.set(b"stateKey", parent_state_bytes(states[7]))
    loaded = store.load()
    assert loaded.last_block_height == 7
    for name in SETS:
        assert facts(getattr(loaded, name)) == facts(getattr(states[7], name))
    assert facts(store.load_validators(8)) == facts(states[7].validators)
    # the node goes on: the new rows rest on the JSON rows
    store.save(states[8])
    store.save(states[9])
    assert full_rows(store, (10, 11)) == []
    assert store_mod._read_record(
        store.db.get(store_mod._valset_key(11)))[0] == 9
    assert facts(store.load_validators(11)) == \
        facts(states[9].next_validators)
    for name in SETS:
        assert facts(getattr(store.load(), name)) == \
            facts(getattr(states[9], name))


def test_begin_block_reads_the_signers_off_the_state_being_applied(chain):
    store, states, blocks = chain
    for h in (8, 9):   # the first blocks signed by the reordered set
        live = build_last_commit_info(blocks[h], None, 1,
                                      states[h - 1].last_validators)
        stored = build_last_commit_info(blocks[h], store, 1)
        assert live == stored
        assert len(live.votes) == len(states[h - 1].last_validators)
    assert len(build_last_commit_info(blocks[9], store, 1).votes) == 5
    assert build_last_commit_info(blocks[1], None, 1,
                                  states[0].last_validators).votes == []


def test_bootstrap_then_load_and_the_heights_around_it(chain):
    _, states, _ = chain
    live = states[9]
    store = Store(MemDB())
    store.bootstrap(live)
    loaded = store.load()
    for name in SETS:
        assert facts(getattr(loaded, name)) == facts(getattr(live, name))
    assert facts(store.load_validators(9)) == facts(live.last_validators)
    assert facts(store.load_validators(10)) == facts(live.validators)
    assert facts(store.load_validators(11)) == facts(live.next_validators)
    assert full_rows(store, range(1, 13)) == [9, 10, 11]
    # the sync goes on from there: the next row rests on bootstrap's
    store.save(states[10])
    assert full_rows(store, range(1, 14)) == [9, 10, 11]
    assert facts(store.load_validators(12)) == \
        facts(states[10].next_validators)
    assert facts(store.load().last_validators) == \
        facts(states[10].last_validators)


def test_a_state_over_another_sets_rows_carries_its_membership_itself():
    """A caller may save a state the rows beneath do not describe (the
    evidence tests' committed state over a hand-saved row): no record
    may then rest on them."""
    gdoc, _ = make_genesis(4)
    state = make_genesis_state(gdoc)
    store = Store(MemDB())
    other = ValidatorSet([Validator.new(deterministic_pv(i).get_pub_key(), 3)
                          for i in range(50, 54)])
    store.save_validator_set(1, other)
    store.save_validator_set(2, other)
    state.last_block_height = 1
    store.save(state)
    loaded = store.load()
    for name in SETS:
        assert facts(getattr(loaded, name)) == facts(getattr(state, name))
    assert facts(store.load_validators(3)) == facts(state.next_validators)
    assert full_rows(store, (1, 2, 3)) == [1, 2, 3]
    assert facts(store.load_validators(2)) == facts(other)


def test_a_record_over_an_overwritten_row_is_refused_not_misread(chain):
    _, states, _ = chain
    store = Store(MemDB())
    store.save(states[0])
    store.save(states[1])   # row 3 rests on row 2
    assert facts(store.load_validators(3)) == facts(states[1].next_validators)
    store.save_validator_set(2, states[9].validators)
    with pytest.raises(ValueError, match="does not hold the membership"):
        store.load_validators(3)
    store.db.delete(store_mod._valset_key(2))
    with pytest.raises(ValueError, match="no validator set row"):
        store.load_validators(3)


def test_membership_digest_names_keys_powers_and_order():
    pks = [deterministic_pv(i).get_pub_key() for i in range(4)]
    vs = ValidatorSet([Validator.new(pk, 10) for pk in pks])
    same = vs.copy()
    same.increment_proposer_priority(3)
    assert same.membership_digest() == vs.membership_digest()
    heavier = vs.copy()
    heavier.update_with_change_set([Validator.new(pks[1], 11)])
    fewer = ValidatorSet([Validator.new(pk, 10) for pk in pks[:3]])
    swapped = vs.copy()
    swapped.validators = list(reversed(swapped.validators))
    digests = {s.membership_digest()
               for s in (vs, heavier, fewer, swapped, ValidatorSet([]))}
    assert len(digests) == 5
