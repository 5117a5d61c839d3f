"""The live consensus path launches ONE lane count, loaded when
consensus starts (ValidatorSet.verify_live, ConsensusState.
_load_programs): on the device path of the CPU backend, a 216-validator
set with `vote_batch_max` = 128 (the shipped 1,024 is minutes of CPU
compile) and a 256-lane arena. Remainders and tails of every length
from 1 to the set's size, and whole heights behind the scripted net,
compile nothing after start. Also tests/test_scale_10k.py's tier-1 twin
on the DEVICE path (tests/test_scripted.py's run on the host)."""

import asyncio

import numpy as np
import pytest

from test_scripted import compare, make_chain, node_config
from tendermint_tpu.consensus import messages as m
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.tpu import ledger
from tendermint_tpu.node import Node
from tendermint_tpu.sim.scripted import HeldVotes, ScriptedNet
from tendermint_tpu.types.sign_batch import VoteSignBatch
from tendermint_tpu.types.vote import VoteType

LANES = 128
N_VALS = 216


class Compiles:
    """benchmark/harness.py CompileWatch: every backend compile, cache
    loads included."""

    def __init__(self):
        from jax import monitoring

        self.names: list[str] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.names.append(kw.get("fun_name", "?"))


COMPILES = Compiles()


def votes_of(chain, height, vtype):
    return [m.decode_consensus_msg(b).vote
            for b in chain.at(height).msgs[vtype]]


def verify_votes(vals, chain_id, votes, sigs):
    def picked(pick):
        return votes if pick is None else [votes[i] for i in pick]

    return vals.verify_live(
        [v.validator_index for v in votes],
        lambda pick: VoteSignBatch(chain_id, picked(pick)),
        lambda pick: [v.sign_bytes(chain_id) for v in picked(pick)],
        sigs, LANES)


@pytest.fixture(scope="module")
def chain():
    return make_chain(N_VALS, 5, seed=5, planted_per_1000=10)


@pytest.fixture(scope="module")
def loaded(chain):
    """The set's live programs, loaded as a starting node loads them."""
    vals = chain.validators
    assert vals.tables_resident()
    vals.load_live_programs(LANES)
    return vals


def structured_since(mark: int):
    return [r for r in ledger.snapshot()[mark:]
            if r["kernel"].startswith("structured")]


def test_every_batch_length_is_one_launch_shape(chain, loaded):
    """1 to 216 votes, a spoiled signature among them: under the device
    threshold the host's, from it up ONE structured launch of LANES
    lanes a LANES votes, a tail under the threshold the host's; the
    verdicts the reference's; nothing compiles."""
    vals = loaded
    votes = votes_of(chain, 1, VoteType.PREVOTE)
    assert len(votes) >= N_VALS - 8
    ledger.reset()
    before = len(COMPILES.names)
    launches = 0
    for n in range(1, len(votes) + 1):
        sigs = [v.signature for v in votes[:n]]
        bad = (n * 7) % n
        sigs[bad] = bytes(64)
        got = verify_votes(vals, chain.chain_id, votes[:n], sigs)
        want = np.ones(n, bool)
        want[bad] = False
        assert (got == want).all(), n
        for lo in range(0, n, LANES):
            launches += min(LANES, n - lo) >= cbatch._DEVICE_THRESHOLD
    assert COMPILES.names[before:] == []
    recs = ledger.snapshot()
    assert {r["kernel"] for r in recs} == {"structured"}
    assert {r["capacity"] for r in recs} == {LANES}
    assert len(recs) == launches


@pytest.mark.parametrize("n", [1, 39, 40, 127, 128, 129, 167, 168, 216])
def test_commit_remainder_of_any_length_is_the_same_program(chain, loaded, n):
    """The lanes of a LastCommit the speculation plane holds no verdict
    for, n of them: verify_commit_lanes_live sends them LANES a launch;
    a spoiled one is named."""
    vals = loaded
    commit = chain.at(2).block.last_commit
    present = [i for i, s in enumerate(commit.signatures)
               if not s.is_absent()]
    slots = present[:min(n, len(present))]
    before, mark = len(COMPILES.names), len(ledger.snapshot())
    got = vals.verify_commit_lanes_live(chain.chain_id, commit, slots, LANES)
    assert got.all()
    assert COMPILES.names[before:] == []
    assert {r["capacity"] for r in structured_since(mark)} <= {LANES}
    # the whole check, as validate_block asks it of a commit the plane
    # never saw: the same launches, and the power tallied
    if n == 216:
        vals.verify_commit(chain.chain_id, commit.block_id, 1, commit,
                           launch_lanes=LANES)
        assert COMPILES.names[before:] == []


def test_batch_that_does_not_fit_the_layout_is_the_hosts(chain, loaded):
    """A vote whose timestamp the structured layout cannot hold (build
    raises ValueError): the batch is verified on the host, with the
    same verdicts, and nothing new is launched or compiled."""
    vals = loaded
    votes = votes_of(chain, 1, VoteType.PRECOMMIT)[:64]
    sigs = [v.signature for v in votes]
    before, mark = len(COMPILES.names), len(ledger.snapshot())

    def build(pick):
        raise ValueError("hostile timestamp")

    got = vals.verify_live(
        [v.validator_index for v in votes], build,
        lambda pick: [votes[i].sign_bytes(chain.chain_id) for i in pick],
        sigs[:-1] + [bytes(64)], LANES)
    assert got[:-1].all() and not got[-1]
    assert COMPILES.names[before:] == []
    assert ledger.snapshot()[mark:] == []


def test_host_forced_set_takes_the_ordinary_ladder(chain):
    prev = cbatch.set_force_host(True)
    try:
        assert not chain.validators.tables_resident()
        assert chain.validators.load_live_programs(LANES) == 0
        votes = votes_of(chain, 1, VoteType.PREVOTE)[:50]
        mark = len(ledger.snapshot())
        got = verify_votes(chain.validators, chain.chain_id, votes,
                           [v.signature for v in votes])
        assert got.all() and ledger.snapshot()[mark:] == []
    finally:
        cbatch.set_force_host(prev)


def test_small_set_loads_nothing_and_launches_as_before():
    small = make_chain(8, 2, seed=1)
    assert not small.validators.tables_resident()
    assert small.validators.load_live_programs(LANES) == 0


async def follow_on_device(tmp_path, chain, upto):
    cfg = node_config(tmp_path, chain.gdoc, timeout_commit_ms=150,
                      vote_batch_max=LANES)
    cfg.speculation.arena_lanes = 256
    node = Node.default_new_node(cfg)
    await node.start()
    # consensus has started: from here nothing may compile
    before = len(COMPILES.names)
    seen0 = len(ledger.snapshot()) + ledger.evicted()
    cs = node.consensus_state
    watch = HeldVotes(cs, chain)
    net = ScriptedNet(chain, 4, query_maj23_s=2.0)
    try:
        await net.attach(node.switch, node.consensus_reactor)
        deadline = asyncio.get_running_loop().time() + 240
        while upto not in watch.precommits:
            assert asyncio.get_running_loop().time() < deadline, \
                (cs.rs.height, cs.rs.step, net.handed_over())
            await asyncio.sleep(0.02)
        net.pause()
        handed = net.handed_over()
        await asyncio.sleep(0.3)
        assert net.handed_over() - handed <= len(net.peers)
        out = {
            "watch": watch, "net": net, "acked": net.acknowledged(),
            "block_ids": {h: node.block_store.load_block_meta(h).block_id
                          for h in range(1, upto + 1)},
            "app_hashes": {h: node.block_store.load_block_meta(
                h + 1).header.app_hash for h in range(1, upto)},
            "seen": {h: node.block_store.load_seen_commit(h)
                     for h in range(1, upto + 1)},
            "compiled": COMPILES.names[before:],
            "records": ledger.snapshot()[-max(1, len(
                ledger.snapshot()) + ledger.evicted() - seen0):],
            "plane": node.speculation,
        }
    finally:
        await net.stop(node.switch)
        await node.stop()
    return out


def test_node_on_the_device_path_compiles_nothing_after_start(
        tmp_path, chain, loaded):
    """The ordinary node behind the scripted net at 216 validators,
    its launches on the (CPU backend's) device path: prevote bursts to
    the polka, precommits to the commit, each next block's LastCommit
    checked by the plane or on the tables; the model's block IDs, app
    hashes and members; every vote launch LANES lanes, the arena's its
    one capacity, no general-kernel launch, and no compile after
    ConsensusState.start returned."""
    from tendermint_tpu.libs import tracing

    tracing.TRACER.clear()
    got = asyncio.run(follow_on_device(tmp_path, chain, upto=3))
    # (not whole sets: a CPU-backend launch is 0.1 s, and a prevote
    # that arrives after its height committed is dropped)
    compare(chain, got, 3, whole_sets=False)
    assert got["compiled"] == []
    by_kernel: dict[str, set] = {}
    for r in got["records"]:
        by_kernel.setdefault(f"{r['workload']}/{r['kernel']}",
                             set()).add(r["capacity"])
    assert by_kernel.get("votes/structured") == {LANES}, by_kernel
    assert not any(k.endswith("/general") for k in by_kernel), by_kernel
    assert by_kernel.get("consensus/structured", {LANES}) == {LANES}
    arena = [k for k in by_kernel if k.startswith("speculation/resident")]
    # (one capacity: 256 lanes asked, bucketed a shard on the CPU mesh)
    assert arena and all(len(by_kernel[k]) == 1 for k in arena), by_kernel
    assert all(r["compile_cache"] != "miss" for r in got["records"])
    loads = [r for r in tracing.TRACER.snapshot()
             if r[0] == tracing.CONSENSUS_LOAD_PROGRAMS]
    assert len(loads) == 1 and loads[0][6]["programs"] >= 1
    assert got["plane"].hits + sum(got["plane"].misses.values()) > 0


def test_plane_loads_its_arena_once_and_honours_host_forced(chain, loaded):
    """SpeculationPlane.load_programs: the arena built, every splice
    bucket, the launch and the clear run once, the set's keys
    installed; a flush of any size compiles nothing afterwards; a
    second call loads nothing; with the host forced the plane neither
    loads nor launches on the device."""
    from tendermint_tpu.config import SpeculationConfig
    from tendermint_tpu.consensus.speculation import SpeculationPlane

    vals = loaded
    cfg = SpeculationConfig()
    cfg.arena_lanes = 256
    prev = cbatch.set_force_host(True)
    try:
        forced = SpeculationPlane(cfg)
        assert forced.load_programs(vals) == 0 and forced._arena is None
        hs = chain.at(1)
        forced.begin_height(chain.chain_id, vals, 1, 0, hs.block_id)
        for v in votes_of(chain, 1, VoteType.PRECOMMIT)[:60]:
            forced.observe_precommit(v)
        mark = len(ledger.snapshot())
        forced.flush_sync()
        assert forced._arena is None and ledger.snapshot()[mark:] == []
        forced.close()
    finally:
        cbatch.set_force_host(prev)
    plane = SpeculationPlane(cfg)
    assert plane.load_programs(vals) >= 3
    assert plane.load_programs(vals) == 0
    before = len(COMPILES.names)
    votes = votes_of(chain, 1, VoteType.PRECOMMIT)
    plane.begin_height(chain.chain_id, vals, 1, 0, chain.at(1).block_id)
    at = 0
    for k in (40, 41, 64):      # three flushes, two splice buckets
        for v in votes[at:at + k]:
            plane.observe_precommit(v)
        at += k
        plane.flush_sync()
    assert COMPILES.names[before:] == []
    commit = chain.at(2).block.last_commit
    assert plane.serve_commit(vals, chain.chain_id, commit.block_id, 1,
                              commit, launch_lanes=LANES)
    assert COMPILES.names[before:] == []
    assert plane.misses["unpatched"] > 0    # the lanes never observed
    plane.close()


def test_a_set_without_tables_starts_without_a_turn_of_the_loop(monkeypatch):
    """ConsensusState.start asks the set's question on the loop and
    goes to the executor only to load: a small net's nodes, started
    one after another, must not yield in between (their first rounds
    run in step, and test nets that deliver each message once lean on
    it: with an executor hop in every start, 4 runs of 6 of
    tests/test_consensus.py::test_non_validator_node_follows hung)."""
    from helpers import make_genesis
    from test_consensus import Node

    async def go():
        gdoc, _ = make_genesis(4)
        node = Node(gdoc, None)
        await node.start()
        loop = asyncio.get_running_loop()

        def no_executor(*a, **kw):
            raise AssertionError("left the loop")

        try:
            monkeypatch.setattr(loop, "run_in_executor", no_executor)
            turns = []
            loop.call_soon(turns.append, 1)
            await node.cs._load_programs()     # what its start ran
            assert turns == []      # no other callback ran meanwhile
        finally:
            monkeypatch.undo()
            await node.stop()

    asyncio.run(go())
