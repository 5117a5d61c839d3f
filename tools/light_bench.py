"""Light-client verification benchmark (reference:
light/client_benchmark_test.go): sequential vs bisection verification
over a synthetic chain, plus the underlying commit-verify cost.

    python tools/light_bench.py [--cpu] [--heights 64] [--vals 32]

Concurrent-serving mode (`--clients N`) drives the light SERVING PLANE
(light/serving.py) instead of the raw client: N concurrent clients fan
out over `--span` distinct heights in two waves (cold, then warm), and
the run emits a BENCH-style JSON line — requests/s, verify launches by
backend, mean lanes per launch, cache hit ratio, coalesce count — so
the serving plane enters the perf trajectory alongside bench.py's
lines:

    python tools/light_bench.py --cpu --clients 64 --span 8
"""

import asyncio
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_chain(n_heights: int, n_vals: int):
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import (
        BlockID, Commit, CommitSig, BlockIDFlag, Header, PartSetHeader,
    )
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    chain_id = "light-bench"
    t0 = 1_700_000_000 * 1_000_000_000
    privs = [
        ed25519.Ed25519PrivKey(hashlib.sha256(b"lb%d" % i).digest())
        for i in range(n_vals)
    ]
    vals = ValidatorSet(
        [Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    blocks = {}
    prev_bid = None
    for h in range(1, n_heights + 1):
        header = Header(
            version_block=11, version_app=0, chain_id=chain_id,
            height=h, time=t0 + h * 10**9, last_block_id=prev_bid,
            last_commit_hash=b"\x01" * 32, data_hash=b"\x02" * 32,
            validators_hash=vals.hash(), next_validators_hash=vals.hash(),
            consensus_hash=b"\x03" * 32, app_hash=b"\x04" * 32,
            last_results_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
            proposer_address=vals.get_proposer().address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
        sigs = []
        for idx, val in enumerate(vals.validators):
            vote = Vote(type=VoteType.PRECOMMIT, height=h, round=0,
                        block_id=bid, timestamp=header.time + 1,
                        validator_address=val.address,
                        validator_index=idx)
            sig = by_addr[val.address].sign(vote.sign_bytes(chain_id))
            sigs.append(CommitSig(BlockIDFlag.COMMIT, val.address,
                                  header.time + 1, sig))
        commit = Commit(h, 0, bid, sigs)
        blocks[h] = LightBlock(SignedHeader(header, commit), vals)
        prev_bid = bid
    return chain_id, blocks


def serving_bench(n_clients: int, n_heights: int, n_vals: int,
                  span: int) -> dict:
    """Drive the serving PLANE (not the raw client) with n_clients
    concurrent requests over `span` distinct heights, two waves —
    the in-process shape of a proxy fleet serving read-mostly
    traffic. Returns (and prints) the BENCH-style record."""
    from tendermint_tpu.config import LightConfig
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.libs.metrics import light_metrics
    from tendermint_tpu.light import (
        Client, LightStore, ServingPlane, TrustOptions,
    )
    from tendermint_tpu.light.provider import BlockNotFoundError, Provider

    chain_id, blocks = build_chain(n_heights, n_vals)
    span = max(1, min(span, n_heights - 1))
    heights = list(range(n_heights - span + 1, n_heights + 1))
    print(f"serving plane: {n_clients} clients x 2 waves over "
          f"{span} distinct heights ({n_vals} validators)")

    class P(Provider):
        async def light_block(self, height):
            if height == 0:
                height = max(blocks)
            lb = blocks.get(height)
            if lb is None:
                raise BlockNotFoundError(str(height))
            return lb

    now = blocks[1].time() + (n_heights + 100) * 10**9
    period = 3600 * 10**9 * 24 * 365
    cl = Client(chain_id,
                TrustOptions(period_ns=period, height=1,
                             hash=blocks[1].hash()),
                P(), [], LightStore(MemDB()), now_fn=lambda: now)
    plane = ServingPlane(cl, LightConfig())
    met = light_metrics()

    def launches():
        return {b: int(met.verify_launches.value(backend=b))
                for b in ("device", "host", "host_recheck")}

    before = launches()
    lanes0 = (met.batch_lanes.count, met.batch_lanes.sum)

    async def wave():
        await asyncio.gather(*(plane.get_verified(heights[i % span])
                               for i in range(n_clients)))

    async def run():
        t0 = time.perf_counter()
        await wave()       # cold: every height verifies (coalesced)
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        await wave()       # warm: the cache answers
        t_warm = time.perf_counter() - t0
        return t_cold, t_warm

    t_cold, t_warm = asyncio.run(run())
    after = launches()
    n_launches = {b: after[b] - before[b] for b in after
                  if after[b] - before[b]}
    total_launches = sum(n_launches.values())
    d_count = met.batch_lanes.count - lanes0[0]
    d_sum = met.batch_lanes.sum - lanes0[1]
    requests = 2 * n_clients
    hits = plane.cache_hits
    record = {
        "metric": "light_serving_requests_per_s",
        "unit": "req/s",
        "value": round(requests / (t_cold + t_warm), 1),
        "clients": n_clients,
        "distinct_heights": span,
        "requests": requests,
        "cold_wave_ms": round(t_cold * 1e3, 2),
        "warm_wave_ms": round(t_warm * 1e3, 2),
        "verify_launches": n_launches,
        "lanes_per_launch": round(d_sum / d_count, 1) if d_count else 0,
        "cache_hit_ratio": round(hits / requests, 3),
        "requests_coalesced": plane.coalesced,
        "shed": dict(plane.sheds),
    }
    # more launches than distinct heights is a coalescing regression
    # ONLY when the launches were not lane-full: with huge valsets a
    # single step's checks exceed the collector's batch_max and a
    # perfectly coalescing plane legitimately splits across launches
    mean_lanes = d_sum / d_count if d_count else 0
    assert total_launches <= span or \
        mean_lanes >= plane.collector.batch_max / 2, (
            f"coalescing regressed: {total_launches} launches for "
            f"{span} distinct heights at {mean_lanes:.0f} lanes/launch")
    plane.close()
    print(json.dumps(record), flush=True)
    return record


def main():
    if "--cpu" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()
    n_heights, n_vals, n_clients, span = 64, 32, 0, 8
    for i, a in enumerate(sys.argv):
        if a == "--heights":
            n_heights = int(sys.argv[i + 1])
        elif a == "--vals":
            n_vals = int(sys.argv[i + 1])
        elif a == "--clients":
            n_clients = int(sys.argv[i + 1])
        elif a == "--span":
            span = int(sys.argv[i + 1])
    if n_clients > 0:
        serving_bench(n_clients, n_heights, n_vals, span)
        return

    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.light import (
        Client, LightStore, TrustOptions,
    )
    from tendermint_tpu.light.provider import (
        BlockNotFoundError, Provider,
    )

    chain_id, blocks = build_chain(n_heights, n_vals)
    print(f"chain: {n_heights} heights x {n_vals} validators")

    class P(Provider):
        async def light_block(self, height):
            if height == 0:
                height = max(blocks)
            lb = blocks.get(height)
            if lb is None:
                raise BlockNotFoundError(str(height))
            return lb

    now = blocks[1].time() + (n_heights + 100) * 10**9
    hour = 3600 * 10**9 * 24 * 365

    async def bisect():
        cl = Client(chain_id,
                    TrustOptions(period_ns=hour, height=1,
                                 hash=blocks[1].hash()),
                    P(), [], LightStore(MemDB()), now_fn=lambda: now)
        t = time.perf_counter()
        await cl.verify_light_block_at_height(n_heights)
        return time.perf_counter() - t

    async def sequential():
        cl = Client(chain_id,
                    TrustOptions(period_ns=hour, height=1,
                                 hash=blocks[1].hash()),
                    P(), [], LightStore(MemDB()), now_fn=lambda: now)
        await cl.initialize()
        t = time.perf_counter()
        trusted = cl.store.latest()
        from tendermint_tpu.light.verifier import verify_adjacent

        for h in range(2, n_heights + 1):
            verify_adjacent(chain_id, trusted, blocks[h], hour, now)
            trusted = blocks[h]
        return time.perf_counter() - t

    async def backwards():
        cl = Client(chain_id,
                    TrustOptions(period_ns=hour, height=1,
                                 hash=blocks[1].hash()),
                    P(), [], LightStore(MemDB()), now_fn=lambda: now)
        await cl.verify_light_block_at_height(n_heights)
        t = time.perf_counter()
        await cl.verify_light_block_at_height(2)
        return time.perf_counter() - t

    b = asyncio.run(bisect())
    s = asyncio.run(sequential())
    w = asyncio.run(backwards())
    print(f"bisection to height {n_heights}:  {b * 1e3:8.1f} ms")
    print(f"sequential (adjacent x{n_heights - 1}): {s * 1e3:8.1f} ms "
          f"({s / (n_heights - 1) * 1e3:.1f} ms/header)")
    print(f"backwards walk {n_heights}->2:   {w * 1e3:8.1f} ms")


if __name__ == "__main__":
    main()
