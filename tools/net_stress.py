"""Liveness wedge hunter: run many short in-process 4-validator nets
(optionally with a maverick misbehavior) and fail loudly on any net
that stalls — full round-state dump included.

This is the harness that found the round-4 lost-advert wedge (a node
stuck in COMMIT forever because its one-shot NewValidBlock broadcast
was lost): the per-run cost is ~1.5 s, so hundreds of independent
net startups — where the rare interleavings live — fit in minutes,
unlike the e2e subprocess runner.

    python tools/net_stress.py [--runs 100] [--misbehavior double-propose]
                               [--target-height 4] [--stall 25]

--overload turns each run into the overload driver behind the e2e
`overload` perturbation (docs/CHAOS.md runbook): a device.verify delay
failpoint throttles verification while a gossip flood (stale block
parts via tx_flood, the same pacing loop the e2e runner uses) hammers
node0's consensus funnel — the net must still reach the target height
with shed counters climbing and every tracked queue inside its bound.

    python tools/net_stress.py --overload [--runs 20] [--flood-rate 500]

--speculation runs each net with the verify-ahead plane enabled
(consensus/speculation.py) and, after the target height, pins the
claim against the tracer rollup: speculation hits happened on every
node, reconcile spans were recorded for them, and a hit's commit-time
verify is reconcile-only (the hit counter only moves when ZERO
fallback lanes verified at commit).

    python tools/net_stress.py --speculation [--runs 10]
"""

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
from tendermint_tpu.libs import jaxcache  # noqa: E402

jaxcache.configure()


def _dump(nodes) -> None:
    for j, n in enumerate(nodes):
        rs = n.cs.rs
        print(f"  node{j}: h={rs.height} r={rs.round} step={rs.step} "
              f"locked_r={rs.locked_round} valid_r={rs.valid_round} "
              f"proposal={'Y' if rs.proposal else 'N'} "
              f"pblock={'Y' if rs.proposal_block else 'N'} "
              f"parts={'Y' if rs.proposal_block_parts else 'N'}",
              flush=True)
        if rs.votes is not None:
            for r in range(max(0, rs.round - 1), rs.round + 1):
                pv = rs.votes.prevotes(r)
                pc = rs.votes.precommits(r)
                print(f"    r{r}: prevotes="
                      f"{pv.sum if pv else '-'} "
                      f"precommits={pc.sum if pc else '-'}", flush=True)


async def one(i: int, misbehavior: str, target_h: int,
              stall_s: float, overload: bool = False,
              flood_rate: float = 500.0,
              speculation: bool = False) -> bool:
    from p2p_harness import make_net

    from tendermint_tpu.consensus.misbehavior import MISBEHAVIORS

    nodes = await make_net(4, speculation=speculation)
    flood_task = None
    spec_rec0 = 0
    if speculation:
        # the TRACER ring is process-global and survives across runs:
        # the reconcile-span pin must compare DELTAS or every run
        # after the first trivially passes on run 0's spans
        from tendermint_tpu.libs.tracing import TRACER

        spec_rec0 = TRACER.stage_rollup(prefix="speculation.").get(
            "speculation.reconcile", {}).get("count", 0)
    try:
        if overload:
            from tendermint_tpu.consensus import messages as cm
            from tendermint_tpu.crypto import merkle
            from tendermint_tpu.e2e.runner import tx_flood
            from tendermint_tpu.libs import failpoints
            from tendermint_tpu.types.block import Part

            failpoints.arm("device.verify", "delay", delay_ms=10.0)
            # stale-height block parts: decodable, cheap to reject,
            # and exactly the bulk-data class the funnel must shed
            # without starving votes
            _root, proofs = merkle.proofs_from_byte_slices([b"x" * 256])
            part_msg = cm.BlockPartMessage(
                height=1, round=0,
                part=Part(0, b"x" * 256, proofs[0]))

            async def submit(_tx: bytes) -> None:
                nodes[0].cs.add_peer_msg_nowait(part_msg, "flooder")

            flood_task = asyncio.get_event_loop().create_task(
                tx_flood(submit, flood_rate, stall_s * 2))
        if misbehavior:
            # Stay inside the f=1 byzantine bound: PROPOSER-triggered
            # misbehaviors (double-propose) fire only on the height-2
            # proposer, so installing on every node still yields
            # exactly ONE equivocator per run (and makes the scenario
            # deterministic); VOTER-triggered ones (double-prevote)
            # fire on every installed node, so they go on a single
            # maverick — four equivocating voters would exceed f=1 and
            # any stall would be protocol-legal, not a bug.
            targets = nodes if "propose" in misbehavior else [nodes[3]]
            for n in targets:
                n.cs.misbehaviors[2] = MISBEHAVIORS[misbehavior]()
        deadline = time.monotonic() + max(60.0, stall_s * 3)
        last_view, last_change = None, time.monotonic()
        while True:
            view = tuple((n.cs.rs.height, n.cs.rs.round,
                          int(n.cs.rs.step)) for n in nodes)
            if all(h >= target_h for h, _, _ in view):
                if speculation:
                    return _check_speculation(i, nodes, spec_rec0)
                return True
            now = time.monotonic()
            if view != last_view:
                last_view, last_change = view, now
            if now - last_change > stall_s or now > deadline:
                print(f"RUN {i} WEDGED: view={view}", flush=True)
                _dump(nodes)
                return False
            await asyncio.sleep(0.1)
    finally:
        if flood_task is not None:
            flood_task.cancel()
            from tendermint_tpu.libs import failpoints
            from tendermint_tpu.libs.metrics import overload_metrics

            failpoints.disarm_all()
            shed = overload_metrics().shed.value(
                queue="consensus.funnel.data")
            print(f"  run {i}: funnel.data shed so far {shed:.0f}",
                  flush=True)
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


def _check_speculation(i: int, nodes, rec0: int = 0) -> bool:
    """Pin the verify-ahead contract against the tracer rollup: the
    net produced speculation hits, and every hit's commit-time verify
    was reconcile-only — the hit counter only moves when ZERO fallback
    lanes verified at commit, and the rollup must show the reconcile
    spans those serves recorded. `rec0` is the reconcile-span count
    before this run (the ring is process-global): only the DELTA
    counts, so the pin stays meaningful on every run, not just run 0."""
    from tendermint_tpu.libs.tracing import TRACER

    hits = sum(n.cs.speculation.hits for n in nodes
               if n.cs.speculation is not None)
    misses: dict[str, int] = {}
    for n in nodes:
        if n.cs.speculation is None:
            continue
        for k, v in n.cs.speculation.misses.items():
            if v:
                misses[k] = misses.get(k, 0) + v
    rec = TRACER.stage_rollup(prefix="speculation.").get(
        "speculation.reconcile", {})
    rec_delta = rec.get("count", 0) - rec0
    print(f"  run {i}: speculation hits={hits} misses={misses} "
          f"reconcile spans={rec_delta} "
          f"p50={rec.get('p50_ms', 0)}ms", flush=True)
    if hits == 0:
        print(f"RUN {i} FAILED: no speculation hits", flush=True)
        return False
    if rec_delta < hits:
        print(f"RUN {i} FAILED: {hits} hits but only "
              f"{rec_delta} new reconcile spans in the rollup",
              flush=True)
        return False
    return True


async def main() -> int:
    runs, mis, target_h, stall = 100, "", 4, 25.0
    overload, flood_rate, speculation = False, 500.0, False
    args = sys.argv
    for i, a in enumerate(args):
        if a == "--runs":
            runs = int(args[i + 1])
        elif a == "--misbehavior":
            mis = args[i + 1]
        elif a == "--target-height":
            target_h = int(args[i + 1])
        elif a == "--stall":
            stall = float(args[i + 1])
        elif a == "--overload":
            overload = True
        elif a == "--flood-rate":
            flood_rate = float(args[i + 1])
        elif a == "--speculation":
            speculation = True
    import jax

    jax.config.update("jax_platforms", "cpu")
    wedges = 0
    t0 = time.monotonic()
    for i in range(runs):
        if not await one(i, mis, target_h, stall, overload=overload,
                         flood_rate=flood_rate,
                         speculation=speculation):
            wedges += 1
        if (i + 1) % 25 == 0:
            print(f"progress: {i + 1}/{runs}, {wedges} wedges, "
                  f"{time.monotonic() - t0:.0f}s", flush=True)
    label = "overload" if overload else (
        "speculation" if speculation else (mis or "clean"))
    print(f"net_stress [{label}]: {wedges} wedges / {runs} runs")
    return 1 if wedges else 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
