"""The three cases of a height against the trusted store (reference
client.go verifyLightBlock), held to the plain reference model
(benchmark/reference/light_model.py, which the benchmark's check()
replays too): `Client` AND `ServingPlane` agree with it on served
hashes, error classes, stored heights and the lanes of every step, for
the forward, the middle and the backward case; the middle case equals
the forward case on the same pair of blocks; a forged first answer is
refused with the model's class and nothing of it is stored; after
`load_programs` a burst of plans of every width launches only the
stated lane counts.

Small and seeded: 24 validators, 96 heights, a set that moves every 8
(3 leave, 3 join, 8 re-weighted), made by the benchmark's plain chain
maker and handed to the program as the JSON bodies a node serves.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from benchmark.reference import light_model as model
from benchmark.traffic import light_chain
from benchmark.traffic.light_serve import (
    KIND_OF, Journal, Replayer, ScriptedNode, provider)
from tendermint_tpu.config import LightConfig
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.tracing import TRACER
from tendermint_tpu.light import (
    Client, LightStore, ServingPlane, TrustOptions)
from tendermint_tpu.light.errors import LightClientError

SEED = 42
PERIOD = 168 * 3600 * 10**9
PARAMS = dict(chain_id="hub-test", validators=24, heights=96,
              power_lo=950, power_hi=1050, move_every=8, leave_join=3,
              reweighted=8, absent_pct_max=3, block_interval_s=6,
              planted_every=16)


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(scope="module")
def chain():
    c = light_chain.Chain(
        SEED, PARAMS, lambda items: light_chain.sign_items(SEED, items),
        time.time_ns())
    c.bodies = {h: (light_chain.commit_body(b),
                    light_chain.validators_bodies(b))
                for h, b in c.blocks.items()}
    c.forged_bodies = {h: (light_chain.commit_body(b),
                           light_chain.validators_bodies(b))
                       for h, b in c.forged.items()}
    return c


def _client(chain, root=1, forged=False, journal=None, witnesses=2):
    primary = ScriptedNode(chain.bodies, chain.top,
                           chain.forged_bodies if forged else None,
                           chain.planted, journal)
    return Client(
        chain.chain_id,
        TrustOptions(period_ns=PERIOD, height=root,
                     hash=model.block_hash(chain.blocks[root])),
        provider(primary, "primary"),
        [provider(ScriptedNode(chain.bodies, chain.top), f"w{i}")
         for i in range(witnesses)],
        LightStore(MemDB()))


async def _plane(chain, root=1, forged=False):
    journal = Journal()
    client = _client(chain, root, forged, journal)
    await client.initialize()   # as `cmd light` does, ahead of the plane
    plane = ServingPlane(client, LightConfig(flush_ms=1.0))
    plane.collector.device_threshold = 10**9   # the host: no compile
    plane.journal = journal
    return plane


def _model(chain, root=1):
    m = model.LightModel(
        chain.chain_id, PERIOD,
        verify_sig=lambda k, msg, s: chain.signed.get((k, msg)) == s)
    m.initialize(chain.blocks[root], model.block_hash(chain.blocks[root]))
    return m


def _replayed(chain, plane, root=1):
    rp = Replayer(chain, lambda k, msg, s: chain.signed.get((k, msg)) == s,
                  time.time_ns(), PERIOD)
    if root != 1:
        rp.model = _model(chain, root)
    for event in plane.journal:
        rp.feed(event)
    return rp


# ------------------------------------------------------ the plain pieces


def test_the_model_imports_nothing_of_the_program():
    """What the model says is its own: it and the copies it leans on
    (canonical sign bytes, the ZIP-215 verifier, the Merkle tree) name
    no module of the program."""
    import ast
    import sys

    for name in ("light_model", "canonical", "ed25519_zip215",
                 "valset_model"):
        with open(sys.modules[model.__name__].__file__.replace(
                "light_model", name)) as f:
            tree = ast.parse(f.read())
        imported = {a.name for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names} \
            | {n.module for n in ast.walk(tree)
               if isinstance(n, ast.ImportFrom)}
        assert not [m for m in imported if "tendermint_tpu" in (m or "")]


@pytest.mark.parametrize("height", [1, 2, 8, 9, 50, 96])
def test_bodies_decode_to_the_models_block(chain, height):
    """The chain's wire bodies through RPCProvider's decode: the
    program's header hash, set hash and commit checks agree with the
    plain chain and the model's lanes."""
    lb = run(provider(ScriptedNode(chain.bodies, chain.top), "p")
             .light_block(height))
    block = chain.blocks[height]
    lb.validate_basic(chain.chain_id)
    assert lb.hash() == model.block_hash(block)
    assert lb.validator_set.hash() == block["header"]["validators_hash"]
    sh = lb.signed_header
    plan = lb.validator_set.plan_commit_light(
        chain.chain_id, sh.commit.block_id, height, sh.commit)
    assert plan.lanes == _model(chain).light_lanes(block)
    plan.execute()


def test_light_block_before(chain):
    store = LightStore(MemDB())
    blocks = {h: run(provider(ScriptedNode(chain.bodies, chain.top), "p")
                     .light_block(h)) for h in (3, 9, 40)}
    assert store.light_block_before(10) is None and \
        store.height_before(10) == 0
    for lb in blocks.values():
        store.save(lb)
    assert [store.height_before(h) for h in (3, 4, 9, 10, 40, 41, 96)] \
        == [0, 3, 3, 9, 9, 40, 40]
    assert store.light_block_before(40).hash() == blocks[9].hash()
    assert store.light_block_before(3) is None
    store.delete(9)
    assert store.height_before(40) == 3 and store.lowest_height() == 3
    assert LightStore(store.db).height_before(41) == 40   # from the db


# --------------------------------------------- Client beside the model

CASES = {
    # name: (root, heights asked in order, the case of the last one)
    "forward": (1, [20, 60], model.FORWARD),
    "forward-adjacent": (1, [2], model.FORWARD),
    "between": (1, [96, 40], model.BETWEEN),
    "between-adjacent": (1, [96, 40, 41], model.BETWEEN),
    "between-two-below": (1, [96, 40, 20, 30], model.BETWEEN),
    "backward": (50, [30], model.BACKWARD),
    "backward-then-between": (50, [30, 40], model.BETWEEN),
}


def _client_steps() -> list[tuple[str, int]]:
    """(form, lanes) of every commit check the serial client ran."""
    return [(r[6]["form"], r[6]["lanes"]) for r in TRACER.snapshot()
            if r[0] == tracing.VERIFY_COMMIT]


@pytest.mark.parametrize("name", sorted(CASES))
def test_client_agrees_with_the_model(chain, name):
    root, asked, case = CASES[name]
    cl, m = _client(chain, root), _model(chain, root)
    run(cl.initialize())
    now = time.time_ns()
    for height in asked:
        TRACER.clear()
        want = m.verify(height, lambda h: chain.blocks[h], now)
        lb = run(cl.verify_light_block_at_height(height))
        assert lb.hash() == want["served"]
        checks = [c for t, b, lt, lo, verdict in want["steps"]
                  if verdict == "ok"
                  for c in ([("trusting", lt)] if lt else [])
                  + [("light", lo)]]
        # (a step that cannot be trusted builds no plan on either side)
        assert _client_steps() == checks
    assert want["case"] == case
    assert cl.store.heights() == m.heights()


# ---------------------------------------- ServingPlane beside the model


@pytest.mark.parametrize("name", sorted(CASES))
def test_plane_agrees_with_the_model(chain, name):
    root, asked, case = CASES[name]

    async def go():
        plane = await _plane(chain, root)
        try:
            for height in asked:
                lb = await plane.get_verified(height)
                assert lb.hash() == model.block_hash(chain.blocks[height])
            return plane, plane.client.store.heights()
        finally:
            plane.close()

    plane, stored = run(go())
    rp = _replayed(chain, plane, root)
    assert rp.wrong == []
    assert stored == rp.model.heights()
    assert plane.hash_walks == (case == model.BACKWARD or
                                name == "backward-then-between")
    assert plane.steps == rp.steps


def test_the_scan_after_a_latest_is_never_a_hash_walk(chain):
    """A wallet's `latest`, then an indexer's scan of every height, a
    dozen requests in flight: each scan height costs signature-verified
    steps from the closest trusted block below, and the model replayed
    over the plane's own order of events decides every one alike."""
    async def go():
        plane = await _plane(chain)
        try:
            top = await plane.get_verified(0)
            assert top.height() == chain.top
            pending = list(range(2, chain.top))

            async def caller():
                while pending:
                    h = pending.pop(0)
                    lb = await plane.get_verified(h)
                    assert lb.hash() == model.block_hash(chain.blocks[h])

            await asyncio.gather(*(caller() for _ in range(12)))
            return plane, plane.client.store.heights()
        finally:
            plane.close()

    plane, stored = run(go())
    rp = _replayed(chain, plane)
    assert rp.wrong == [] and rp.walks_after_latest == 0
    assert plane.hash_walks == 0 and plane.steps == rp.steps >= chain.top - 2
    assert stored == rp.model.heights() == list(range(1, chain.top + 1))
    # every lane the plane verified is a lane the model selected
    assert sum(rp.lanes_ok.values()) == sum(
        ev[4] + ev[5] for ev in plane.journal
        if ev[0] == "step" and ev[6] == "ok")


def test_between_is_the_forward_check_on_the_same_pair(chain):
    """40 verified from 30: once as the height above the latest trusted
    block (forward), once between 30 and a trusted 96 (the middle
    case). Same checks, same lanes, same verdict."""
    async def go(first):
        plane = await _plane(chain)
        try:
            for h in first:
                await plane.get_verified(h)
            mark = len(plane.journal)
            await plane.get_verified(40)
            return [ev[2:7] for ev in plane.journal[mark:]
                    if ev[0] == "step"]
        finally:
            plane.close()

    forward = run(go([30]))
    between = run(go([96, 30]))
    assert forward == between and len(forward) == 1
    assert forward[0][:2] == (30, 40) and forward[0][4] == "ok"
    assert forward[0][2] > 0 and forward[0][3] > 0


# --------------------------------------------------- forged first answers


@pytest.mark.parametrize("kind", light_chain.FORGED)
def test_a_forged_first_answer_is_refused_and_not_stored(chain, kind):
    """Client and plane refuse the forged block with the model's class,
    store nothing of it, and serve the true header when asked again."""
    height = next(h for h, k in chain.planted.items() if k == kind)
    true_hash = model.block_hash(chain.blocks[height])
    m = _model(chain)
    want = m.verify(height, lambda h: chain.forged[h] if h == height
                    else chain.blocks[h], time.time_ns())
    assert want["refused"] == model.INVALID and height not in m.store

    cl = _client(chain, forged=True)
    with pytest.raises((LightClientError, ValueError)) as e:
        run(cl.verify_light_block_at_height(height))
    assert KIND_OF[type(e.value).__name__] == want["refused"]
    assert cl.store.get(height) is None
    assert cl.store.heights() == m.heights()   # the pivots, as the model
    assert run(cl.verify_light_block_at_height(height)).hash() == true_hash

    async def go():
        plane = await _plane(chain, forged=True)
        try:
            with pytest.raises((LightClientError, ValueError)) as e:
                await plane.get_verified(height)
            assert KIND_OF[type(e.value).__name__] == want["refused"]
            assert plane.client.store.get(height) is None
            assert plane.cache.get(height, time.time_ns()) is None
            lb = await plane.get_verified(height)
            assert lb.hash() == true_hash
            return plane
        finally:
            plane.close()

    rp = _replayed(chain, run(go()))
    assert rp.wrong == []


def test_a_bad_overlap_signature_ends_the_verification(chain):
    """A signature of the TRUSTED set's overlap that does not verify is
    a forged commit (reference VerifyNonAdjacent: only too little power
    becomes ErrNewValSetCantBeTrusted): no bisection, on either path."""
    from tendermint_tpu.light.errors import VerificationFailedError
    from tendermint_tpu.light.verifier import verify

    node = ScriptedNode(chain.bodies, chain.top)
    trusted = run(provider(node, "p").light_block(2))
    target = run(provider(node, "p").light_block(6))
    sigs = target.signed_header.commit.signatures
    first = next(i for i, cs in enumerate(sigs) if cs.signature)
    sigs[first].signature = bytes(64)
    with pytest.raises(VerificationFailedError):
        verify(chain.chain_id, trusted, target, PERIOD, time.time_ns())


# ------------------------------------------------- the plane's launches


def test_launch_lanes_are_a_closed_set():
    from tendermint_tpu.crypto.tpu.verify import LaunchShapes

    shapes = LaunchShapes(256)
    assert [shapes.fit(n) for n in (1, 52, 256, 257, 512, 513)] \
        == [[256], [256], [256], [256, 256], [256, 256], [256] * 3]
    assert LaunchShapes(8).fit(20) == [8, 8, 8]


@pytest.mark.parametrize("msg_len", [3, 47, 48, 110, 175])
def test_a_launch_is_packed_to_the_shapes_blocks(msg_len):
    """Every canonical vote, and the pad triple, is packed to the two
    SHA-512 blocks the plane's programs were loaded at."""
    from tendermint_tpu.crypto.tpu import verify as tv

    dp, _, ds = tv._dummy_triple()
    packed = tv.pack_batch([dp] * 3, [b"m" * msg_len] * 3, [ds] * 3,
                           min_blocks=2)
    assert packed["msg"].shape == (3, 2 * 128 - 64)
    assert tv.pack_batch([dp], [b"m" * msg_len], [ds])["msg"].shape[1] \
        == (128 - 64 if msg_len <= 47 else 2 * 128 - 64)


def test_after_load_programs_every_launch_has_a_stated_lane_count(
        chain, monkeypatch):
    """The plane loads its shapes before the first request, and a burst
    of plans of every width (deadline cuts, full cuts, a plan that goes
    alone) then launches those lane counts and no other. The kernel is
    stood in for by the host oracle: what is pinned is every launch's
    shape, which is what compiles."""
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.crypto.tpu import verify as tv

    launched = []

    def fake_chunk(pubs, msgs, sigs, bucket, rec=None, min_blocks=1):
        launched.append((bucket, min_blocks, len(pubs)))
        if rec is not None:
            rec.capacity = bucket
        out = np.ones(bucket, bool)
        out[:len(pubs)] = [Ed25519PubKey(p).verify_signature(m, s)
                           for p, m, s in zip(pubs, msgs, sigs)]
        return out

    monkeypatch.setattr(tv, "_launch_chunk", fake_chunk)
    cbatch.reset_breakers()

    async def go():
        plane = ServingPlane(_client(chain), LightConfig(
            batch_max=64, flush_ms=1.0))
        try:
            assert plane.collector.shapes.lanes == 64
            await plane.load_programs()
            loads = list(launched)
            node = ScriptedNode(chain.bodies, chain.top)
            plans = []
            for h in (2, 9, 30, 60, 90):
                lb = await provider(node, "p").light_block(h)
                sh = lb.signed_header
                full = lb.validator_set.plan_commit_light(
                    chain.chain_id, sh.commit.block_id, h, sh.commit)
                for width in (1, 5, 11, 15, 16):
                    plans.append(type(full)(
                        full.valset, full.lanes[:width], full.slots[:width],
                        full.sigs[:width], full.msgs[:width]
                        if isinstance(full.msgs, list)
                        else full.msgs.materialize()[:width], "light"))
            plane.collector.device_threshold = 1
            await asyncio.gather(*(plane.collector.check(p)
                                   for p in plans * 3))
            return loads
        finally:
            plane.close()

    try:
        loads = run(go())
    finally:
        cbatch.reset_breakers()
    assert [(b, k) for b, k, _ in loads] == [(64, 2)]
    rest = launched[len(loads):]
    assert rest and {b for b, _, _ in rest} == {64}
    assert {k for _, k, _ in rest} == {2}
    # the sentinel rides every launch; none holds more than its shape
    assert all(1 < n <= b for b, _, n in rest)
    (span,) = [r for r in TRACER.snapshot()
               if r[0] == tracing.LIGHT_LOAD_PROGRAMS][-1:]
    assert span[6]["lanes"] == 64 and span[6]["programs"] >= 0
