"""The copied light-client model (`reference/light_model.py`) and the
plain chain maker against themselves and against the program's own
types at a small size: header and set hashes, the lanes of both commit
checks with their early exit, the three cases of verifyLightBlock, the
class every forged kind is refused with."""

import asyncio
import time

import pytest

from benchmark.reference import light_model as model
from benchmark.traffic import light_chain
from benchmark.traffic.light_serve import ScriptedNode, provider

SEED = 2147483659
PERIOD = 168 * 3600 * 10**9
P = dict(chain_id="hub-ref", validators=24, heights=64, power_lo=950,
         power_hi=1050, move_every=8, leave_join=3, reweighted=8,
         absent_pct_max=3, block_interval_s=6, planted_every=12)


@pytest.fixture(scope="module")
def chain():
    return light_chain.Chain(
        SEED, P, lambda items: light_chain.sign_items(SEED, items),
        time.time_ns())


def _model(chain, **kw):
    m = model.LightModel(chain.chain_id, PERIOD, **kw)
    m.initialize(chain.blocks[1], model.block_hash(chain.blocks[1]))
    return m


def test_the_chain_is_the_seeds_alone(chain):
    again = light_chain.Chain(
        SEED, P, lambda items: light_chain.sign_items(SEED, items),
        chain.blocks[P["heights"]]["header"]["time"])
    assert [model.block_hash(b) for b in again.blocks.values()] == \
        [model.block_hash(b) for b in chain.blocks.values()]
    assert again.planted == chain.planted
    moved = sum(chain.sets[h] != chain.sets[h + 1]
                for h in range(1, P["heights"] + 1))
    assert moved == P["heights"] // P["move_every"]


@pytest.mark.parametrize("height", [1, 2, 33, 64])
def test_hashes_are_the_programs(chain, height):
    bodies = {h: (light_chain.commit_body(b),
                  light_chain.validators_bodies(b))
              for h, b in chain.blocks.items()}
    lb = asyncio.run(provider(ScriptedNode(bodies, 64), "p")
                     .light_block(height))
    assert lb.hash() == model.block_hash(chain.blocks[height])
    assert lb.validator_set.hash() == \
        chain.blocks[height]["header"]["validators_hash"]
    lb.validate_basic(chain.chain_id)


def test_lanes_stop_at_the_first_that_passes_the_threshold(chain):
    m = _model(chain)
    b = chain.blocks[40]
    own = m.light_lanes(b)
    power = [b["validators"][s][1] for s in own]
    total = sum(p for _, p in b["validators"])
    assert 3 * sum(power) > 2 * total >= 3 * sum(power[:-1])
    lanes, keys = m.trusting_lanes(chain.blocks[30], b)
    trusted = dict(chain.blocks[30]["validators"])
    tp = [trusted[k] for k in keys]
    assert 3 * sum(tp) > sum(trusted.values()) >= 3 * sum(tp[:-1])
    assert lanes == sorted(lanes) and len(lanes) < len(own)


def test_the_three_cases(chain):
    m, now = _model(chain), time.time_ns()

    def fetch(h):
        return chain.blocks[h]

    top = m.verify(64, fetch, now)
    assert top["case"] == model.FORWARD and top["refused"] is None
    assert top["steps"][-1][1] == 64 and top["stored"][-1] == 64
    mid = m.verify(20, fetch, now)
    assert mid["case"] == model.BETWEEN and len(mid["steps"]) == 1
    assert mid["steps"][0][0] == max(h for h in top["stored"] + [1]
                                     if h < 20)
    adj = m.verify(21, fetch, now)
    assert adj["steps"] == [(20, 21, 0, adj["steps"][0][3], "ok")]
    m2 = model.LightModel(chain.chain_id, PERIOD)
    m2.initialize(chain.blocks[30], model.block_hash(chain.blocks[30]))
    back = m2.verify(10, fetch, now)
    assert back["case"] == model.BACKWARD and back["steps"] == []
    assert m2.heights() == [10, 30]


@pytest.mark.parametrize("kind", light_chain.FORGED)
def test_every_forged_kind_is_refused_invalid(chain, kind):
    height = next(h for h, k in chain.planted.items() if k == kind)
    m = _model(chain)
    out = m.verify(height, lambda h: chain.forged[h] if h == height
                   else chain.blocks[h], time.time_ns())
    assert out["refused"] == model.INVALID and out["served"] is None
    assert height not in m.store
    # with the real verifier (no record): the same verdict
    if kind in ("sig_bit", "s_plus_l"):
        assert (height, m.light_lanes(chain.blocks[height])[-1]) \
            in chain.spoiled


def test_an_expired_trusted_block_is_refused_expired(chain):
    m = _model(chain)
    late = chain.blocks[1]["header"]["time"] + PERIOD
    with pytest.raises(model.Refused) as e:
        m.step(chain.blocks[1], chain.blocks[2], late)
    assert e.value.kind == model.EXPIRED
