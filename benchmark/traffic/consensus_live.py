"""Traffic: a non-validator full node following LIVE consensus on a
chain whose validators are scripted signers behind scripted peers
(`tendermint_tpu/sim/scripted.py`, program code: the tier-1 tests drive
the same). This process runs the node the way `cmd start` does (Config
-> Node.default_new_node -> start), its own key not in the genesis, and
owns the chip. The chain — blocks, proposals, every validator's prevote
and precommit of `heights` heights, each encoded once — is made in
set-up: `gen.py`'s pool of processes signs with OpenSSL.

Parameters (the cell's file): `validators`, `peers`, `heights`,
`warm_heights`, `txs_per_block`, `tx_bytes`, `absent_share_range`,
`bad_signature_votes_per_1000`, `reference_sample` and the limits of
`check()`. The file lists NO launch shape to warm: the node loads its
live path's programs itself when consensus starts (`ConsensusState.
_load_programs`: the set's comb tables, the structured program at
`vote_batch_max` lanes, the speculation arena), every live launch has
that one lane count, and this driver calls nothing but the node's own
start and `warm_heights` whole heights.

`sigs_per_s` = prevote and precommit signatures the node verified AND
tallied (one HasVote each) for heights it committed, between the first
and the last commit inside the window, over the time between those two
commits.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import shutil
import threading
import time

from benchmark import gen
from benchmark.harness import OUT, BenchFailure, say
from benchmark.reference import consensus_model as model
from benchmark.reference import ed25519_zip215 as ref

CHAIN_ID = "bench-consensus"
GENESIS_TIME = 1_753_928_000_000_000_000


def sign_items(seed: int, n: int, items: list) -> list[bytes]:
    """Pool worker: (validator index, sign bytes) -> signatures."""
    if (seed, n) not in gen._KEYS:
        gen._KEYS.clear()
        gen._KEYS[(seed, n)] = gen._ordered(seed, n)[1]
    keys = gen._KEYS[(seed, n)]
    return [keys[i].sign(msg) for i, msg in items]


def _by_tag(records) -> dict:
    """{workload/kernel: [launches, lanes]} of launch-ledger records."""
    out: dict[str, list] = {}
    for r in records:
        k = out.setdefault(f"{r['workload']}/{r['kernel']}", [0, 0])
        k[0] += 1
        k[1] += r["lanes"]
    return out


def _by_shape(records) -> dict:
    """{workload/kernel: {lanes the launch was padded to: launches}}:
    which of the shapes set-up warmed the window met."""
    out: dict[str, dict] = {}
    for r in records:
        k = out.setdefault(f"{r['workload']}/{r['kernel']}", {})
        cap = str(r.get("capacity") or r["lanes"])
        k[cap] = k.get(cap, 0) + 1
    return out


class Driver:
    CONTROLS = ("tallies_unverified_votes", "stale_app_state")

    def __init__(self, run):
        self.run = run
        self.n = run.params["validators"]
        self.home = os.path.join(OUT, "node-" + run.cell.name)
        self.loop = None
        self.node = None
        self.net = None
        self.pool = None
        self.commits: list[tuple[float, int]] = []   # (perf_counter, height)
        self.window = None
        # every backend compile by name: which shape a set-up pays
        # for, and which one a window met first
        self.compiled: list[tuple[str, float]] = []
        self.traced_s = 0.0   # Python's share: tracing and lowering
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_compile)

    def _on_compile(self, event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiled.append((kw.get("fun_name", "?"), round(secs, 2)))
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            self.traced_s += secs

    def _compiled_since(self, mark: int) -> dict:
        out: dict[str, list] = {}
        for name, secs in self.compiled[mark:]:
            k = out.setdefault(name, [0, 0.0])
            k[0] += 1
            k[1] = round(k[1] + secs, 2)
        return out

    def _on_loop(self, coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    # ---------------------------------------------------------- set-up

    def _signer(self):
        seed, n = self.run.seed, self.n
        workers = self.pool._max_workers

        def sign(items):
            if len(items) < 64:
                return sign_items(seed, n, items)
            step = -(-len(items) // workers)
            futs = [self.pool.submit(sign_items, seed, n,
                                     items[lo:lo + step])
                    for lo in range(0, len(items), step)]
            return [sig for f in futs for sig in f.result()]

        return sign

    def setup(self) -> None:
        from tendermint_tpu import cmd
        from tendermint_tpu.abci.kvstore import KVStoreApp
        from tendermint_tpu.config import Config
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.node import Node
        from tendermint_tpu.sim.scripted import (
            HeldVotes, ScriptedChain, ScriptedNet)
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

        run, n, p = self.run, self.n, self.run.params
        t0 = time.perf_counter()
        _, self.pubs = gen.validator_order(run.seed, n)
        self.gdoc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=GENESIS_TIME,
            validators=[GenesisValidator(Ed25519PubKey(pk),
                                         run.config["voting_power"])
                        for pk in self.pubs])
        self.gdoc.validate_and_complete()
        state = make_genesis_state(self.gdoc)
        if [v.pub_key.bytes() for v in state.validators.validators] \
                != self.pubs:
            raise BenchFailure("the program orders the validator set "
                               "differently from the reference")
        self.vals = state.validators

        # the ordinary node, its own key not in the genesis
        shutil.rmtree(self.home, ignore_errors=True)
        os.makedirs(os.path.join(self.home, "config"))
        self.gdoc.save(os.path.join(self.home, "config", "genesis.json"))
        cmd.cmd_init(argparse.Namespace(home=self.home, chain_id=CHAIN_ID))
        path = os.path.join(self.home, "config", "config.toml")
        cfg = Config.load(path)
        for key, value in run.config["config_toml_overrides"].items():
            section, field = key.split(".")
            setattr(getattr(cfg, section), field, value)
        cfg.rpc.laddr = ""
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.save(path)
        cfg = Config.load(path)
        cfg.validate_basic()
        cfg.base.home = self.home
        shipped = Config()
        if (cfg.consensus, cfg.speculation) != (shipped.consensus,
                                                shipped.speculation):
            raise BenchFailure("the cell runs the consensus and "
                               "speculation defaults as shipped")
        if run.rehearse:
            cfg.crypto.backend = "auto"  # no chip to promise
            # the CPU backend: a 12,288-lane arena launch is ~10 s and
            # a 1,024-lane program a minute of compile
            cfg.speculation.arena_lanes = p["arena_lanes"]
            cfg.consensus.vote_batch_max = p["vote_batch_max"]
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="bench-node", daemon=True)
        self._thread.start()

        async def start():
            self.node = Node.default_new_node(cfg)
            await self.node.start()

        # the node starts (its start builds the set's tables and loads
        # the live path's programs, off its loop) while the chain is
        # signed: neither needs the other
        t1 = time.perf_counter()
        mark = len(self.compiled)
        starting = asyncio.run_coroutine_threadsafe(start(), self.loop)
        self.pool = gen.make_pool()
        lo, hi = p["absent_share_range"]
        self.chain = ScriptedChain(
            self.gdoc, KVStoreApp(), self._signer(), heights=p["heights"],
            seed=run.seed, txs_per_block=p["txs_per_block"],
            tx_bytes=p["tx_bytes"], absent_share=(lo, hi),
            planted_per_1000=p["bad_signature_votes_per_1000"])
        self.pool.shutdown(wait=True)
        self.pool = None
        say("chain ready", validators=n, heights=p["heights"],
            signatures=sum(h.signatures() for h in self.chain.heights),
            planted=sum(len(h.planted) for h in self.chain.heights),
            block_bytes=self.chain.heights[-1].parts.byte_size,
            parts=self.chain.heights[-1].parts.total,
            seconds=round(time.perf_counter() - t0, 3))
        starting.result(timeout=900)
        cs = self.node.consensus_state
        self.held = HeldVotes(cs, self.chain)
        if cs.priv_validator_address in {
                v.address for v in cs.rs.validators.validators}:
            raise BenchFailure("the node's own key is in the genesis")
        self.net = ScriptedNet(
            self.chain, p["peers"],
            query_maj23_s=cfg.consensus.peer_query_maj23_sleep_ms / 1000)
        from tendermint_tpu.libs.tracing import TRACER

        loaded = [r[6] for r in TRACER.snapshot()
                  if r[0] == "consensus.load_programs"]
        say("node up", boot_s=round(time.perf_counter() - t1, 3),
            peers=p["peers"], load_programs=loaded,
            compiled=self._compiled_since(mark),
            traced_s=round(self.traced_s, 2))

    # ------------------------------------------------------------ warm

    def warm(self) -> None:
        """`warm_heights` whole heights through the node, and nothing
        else: every program the window launches was loaded by the
        node's own start."""
        run, p = self.run, self.run.params
        cs = self.node.consensus_state
        t0 = time.perf_counter()
        mark = len(self.compiled)
        self._on_loop(self.net.attach(self.node.switch,
                                      self.node.consensus_reactor), 60)
        target = p["warm_heights"]
        while self.node.block_store.height < target:
            if time.perf_counter() - t0 > 240:
                raise BenchFailure(
                    f"the node never committed height {target}: it is at "
                    f"{cs.rs.height}/{cs.rs.round}/{cs.rs.step.name}")
            run.ledger.drain()
            time.sleep(0.05)
        say("warm heights", heights=target,
            seconds=round(time.perf_counter() - t0, 3),
            compiled=self._compiled_since(mark))
        self._compiled_at_window = len(self.compiled)
        run.ledger.drain()
        self._warm_records = len(run.ledger.records)
        # the harness clears the span ring between here and measure()
        self.handed_before_window = self.net.handed_over()

    # --------------------------------------------------------- measure

    @staticmethod
    def _host_lanes_now() -> float:
        from tendermint_tpu.libs.metrics import crypto_metrics

        return crypto_metrics().batch_lanes.value(backend="host")

    @staticmethod
    def _ring_by_kind(top: int = 40) -> dict:
        """{kind: [entries, units, busy ms]} of the program's span ring,
        largest first: where an untraced window went, and what filled
        the ring if it overflowed."""
        from benchmark.layer_metrics.program_span_stat import (
            busy_ms, occurrences)
        from tendermint_tpu.libs.tracing import TRACER

        kinds: dict[str, list] = {}
        for r in TRACER.snapshot():
            k = kinds.setdefault(r[0], [0, 0, 0.0])
            k[0] += 1
            k[1] += occurrences(r)
            k[2] += busy_ms(r)
        order = sorted(kinds, key=lambda k: -kinds[k][2])[:top]
        return {k: [kinds[k][0], kinds[k][1], round(kinds[k][2], 1)]
                for k in order}

    def measure(self, seconds: float) -> dict:
        run = self.run
        store = self.node.block_store
        t0 = time.perf_counter()
        self.handed_at_window = self.net.handed_over()
        if run.trace:
            slice_s = min(run.params.get("trace_slice_s", 2.0), seconds)
            run.counters["trace_slice_from_mono"] = \
                time.monotonic() + (seconds - slice_s) / 2
        plane = self.node.speculation
        missed0 = sum(plane.misses.values()) if plane is not None else 0
        host0 = self._host_lanes_now()
        last = store.height
        last_drain = t0
        while (now := time.perf_counter()) < t0 + seconds:
            h = store.height
            if h > last or now - last_drain > 0.2:
                # every height and five times a second: a window is
                # 220-340 launches and the program's ring holds 512
                run.ledger.drain()
                last_drain = now
            if h > last:
                self.commits.extend((now, k) for k in range(last + 1, h + 1))
                last = h
            time.sleep(0.002)
        t1 = time.perf_counter()
        # the votes of the last committed height that are still on
        # their way are tallied in NewHeight; then the peers pause (a
        # traced run's profiler may go on stopping for two minutes:
        # the node gets nothing to eat meanwhile) and stay connected
        time.sleep(min(1.5, self.node.config.consensus.commit_timeout()
                       + 0.5))
        self.loop.call_soon_threadsafe(self.net.pause)
        # what the peers had handed over is verified and tallied
        cs = self.node.consensus_state
        deadline = time.perf_counter() + 10
        while (cs.peer_funnel.qsize() or not cs._vote_idle.is_set()) \
                and time.perf_counter() < deadline:
            time.sleep(0.01)
        run.ledger.drain()
        self.host_lanes = self._host_lanes_now() - host0
        self.acked = self.net.acknowledged()
        if len(self.commits) < 2:
            raise BenchFailure(
                f"{len(self.commits)} commit(s) inside the window: "
                "sigs_per_s needs two")
        (ta, ha), (tb, hb) = self.commits[0], self.commits[-1]
        sigs = sum(len(self.acked.get((h, t), ()))
                   for h in range(ha + 1, hb + 1) for t in (1, 2))
        self.window = (ha, hb)
        run.counters["heights_committed"] = hb - ha
        # a LastCommit is validated at the prevote, at the precommit
        # and in apply_block: the reader counts the window's
        # speculation.reconcile spans for how often lanes were asked
        run.counters["lastcommit_lanes"] = sum(
            len(self.chain.at(h).lanes[2])
            for h in range(ha, hb)) / (hb - ha)
        if plane is not None:
            run.counters["speculation_lanes_missed"] = \
                sum(plane.misses.values()) - missed0
        if run.trace:
            # where the launches fell around the profiler's slice: the
            # slice ends early only if `trace_slice_launches` records
            # land after its first 0.3 s (harness.TraceSlice._hold)
            at = run.counters["trace_slice_from_mono"]
            say("launches around the slice", offsets_s=[
                [r["workload"][:4], round(r["mono"] - at, 3)]
                for r in run.ledger.records[self._warm_records:]
                if -0.5 <= r["mono"] - at <= 2.0])
        say("window", first_commit=ha, last_commit=hb,
            heights=hb - ha, seconds=t1 - t0, between_s=tb - ta,
            sigs=sigs, handed_over=self.net.handed_over(),
            redelivered=self.net.redelivered(),
            speculation=None if plane is None else
            {"hits": plane.hits, "misses": plane.misses},
            node_at=[cs.rs.height, cs.rs.round, cs.rs.step.name],
            host_verified_lanes=self.host_lanes,
            launches=_by_tag(run.ledger.records),
            shapes=_by_shape(run.ledger.records[self._warm_records:]),
            compiled_in_window=self._compiled_since(
                self._compiled_at_window),
            ring=self._ring_by_kind())
        return {"attempted": hb - ha,
                "failed": sum(1 for h in range(ha + 1, hb + 1)
                              if self.held.rounds.get(h) != 0),
                "metrics": {"sigs_per_s": sigs / (tb - ta)}}

    # ----------------------------------------------------------- check

    def _verifier(self, heights):
        """The copied ZIP-215 verifier on every planted lane and on a
        seeded sample of the others; the generator's own record (an
        OpenSSL signature over these bytes, or a spoiled one) for the
        rest. Returns the function and its tallies."""
        rng = self.run.rng("sample")
        want = self.run.params["reference_sample"]
        total = sum(h.signatures() for h in heights)
        share = min(1.0, want / max(1, total))
        spoiled = {p.signature for h in heights for p in h.planted}
        tally = {"reference": 0, "disagree": 0}

        def verify(pub, msg, sig):
            recorded = sig not in spoiled
            if not recorded or rng.random() < share:
                tally["reference"] += 1
                got = ref.verify(pub, msg, sig)
                tally["disagree"] += got != recorded
                return got
            return recorded

        return verify, tally

    def check(self, control: str | None = None) -> dict:
        from tendermint_tpu.libs.tracing import TRACER
        from tendermint_tpu.sim.scripted import HeldVotes

        store = self.node.block_store
        ha, hb = self.window
        heights = [self.chain.at(h) for h in range(1, hb + 1)]
        verify, tally = self._verifier(heights[ha:])
        follower = model.Follower(
            CHAIN_ID, [(pk, self.run.config["voting_power"])
                       for pk in self.pubs], verify=verify)
        wrong_id = wrong_hash = wrong_seen = foreign = lost = tallied = 0
        unrefused = 0
        unverified = control == "tallies_unverified_votes"
        # the planted votes' fate by the NODE's own records: the
        # signature its vote sets held for each of them (exact), and
        # how many votes its tallies refused (consensus.vote_tally
        # `rejected`, the ring since the window began) against the
        # spoiled copies the peers handed over: never more than those
        # handed over since and those among the votes that can have
        # been in the node when the ring was cleared (a full funnel, a
        # full buffer, a batch and a message a peer), and of those
        # handed over inside the window at least the share the cell's
        # file asks for
        kept = dict(self.held.signatures)
        if unverified:   # what such a node would hold and report
            kept.update({(hs.height, int(pl.type), pl.lane): pl.signature
                         for hs in heights[ha:] for pl in hs.planted})
        spoiled_held = sum(
            1 for hs in heights[ha:] for pl in hs.planted
            if kept.get((hs.height, int(pl.type), pl.lane)) == pl.signature)
        # a HasVote for a planted (height, type, index) before its good
        # copy was handed over: only the spoiled copy can have been
        # tallied then
        early = [k for k in self.net.acknowledged_before_good_copy()
                 if ha < k[0] <= hb]
        if unverified:   # such a node acknowledges the copy it gets first
            early = [(at[1], at[2], at[3]) for at in self.net.planted_at
                     if ha < at[1] <= hb]
        ring = TRACER.snapshot()
        refused = 0 if unverified else sum(
            (r[6] or {}).get("rejected", 0) for r in ring
            if r[0] == "consensus.vote_tally")
        shed = sum((r[6] or {}).get("shed", 0) for r in ring
                   if r[0] == "consensus.vote_queue_wait")
        cc = self.node.config.consensus
        in_node = cc.peer_funnel_votes_size + cc.vote_buf_max \
            + cc.vote_batch_max + len(self.net.peers)
        inside = sum(1 for at in self.net.planted_at
                     if at[0] >= self.handed_at_window)
        around = sum(1 for at in self.net.planted_at
                     if at[0] >= self.handed_before_window - in_node)
        # (not all: a prevote that reaches the routine after its height
        # was committed is dropped for its height, unverified, and the
        # peers that had most to send again start a height last:
        # 3-12 % of a window's copies on the chip; a node that verifies
        # nothing refuses none)
        unrefused_share = 0.0 if TRACER.dropped or not inside else \
            100.0 * max(0, inside - shed - refused) / inside
        for hs in heights:
            h = hs.height
            meta = store.load_block_meta(h)
            if meta is None or meta.block_id != hs.block_id:
                wrong_id += 1
            after = store.load_block_meta(h + 1)
            got_hash = after.header.app_hash if after is not None \
                else self.node.consensus_state.state.app_hash
            if control == "stale_app_state":
                got_hash = heights[max(0, h - 2)].app_hash
            if h <= ha:   # before the window: the app's state only
                for tx in hs.txs:
                    follower.app.deliver(tx)
                wrong_hash += got_hash != follower.app.app_hash()
                continue
            # the planted copies first: they arrive first
            votes = []
            for pl in hs.planted:
                pos = int(hs.lanes[pl.type].searchsorted(pl.lane))
                votes.append((int(pl.type), pl.lane,
                              int(hs.times[pl.type][pos]), pl.signature))
            for vtype in (1, 2):
                votes += zip([vtype] * len(hs.lanes[vtype]),
                             hs.lanes[vtype].tolist(),
                             hs.times[vtype].tolist(), hs.sigs[vtype])
            psh = hs.block_id.part_set_header
            want = follower.follow(h, hs.block_id.hash, psh.total,
                                   psh.hash, hs.txs, votes)
            wrong_hash += got_hash != want.app_hash or \
                want.block_hash != hs.block_id.hash
            unrefused += {(int(pl.type), pl.lane, pl.signature)
                          for pl in hs.planted} != set(want.refused)
            # the sets' members
            held = {1: HeldVotes.members(self.held.prevotes[h]),
                    2: HeldVotes.members(self.held.precommits[h])
                    if h in self.held.precommits else None}
            for vtype in (1, 2):
                acked = self.acked.get((h, vtype), set())
                if held[vtype] is None:    # the window's last height
                    held[vtype] = acked
                foreign += len(held[vtype] - want.members[vtype])
                lost += len(acked - held[vtype])
                # a commit needs > 2/3 of the precommits; prevotes that
                # were shed and come back late may leave the polka
                # behind it
                if vtype == 2 and \
                        not follower.holds_two_thirds(held[vtype]):
                    wrong_seen += 1
            # the seen commit: > 2/3, only signatures that were sent
            seen = store.load_seen_commit(h)
            sent = dict(zip(hs.lanes[2].tolist(), hs.sigs[2]))
            signed = [i for i, s in enumerate(seen.signatures)
                      if not s.is_absent()]
            if seen.block_id != hs.block_id \
                    or not follower.holds_two_thirds(signed):
                wrong_seen += 1
            got_sig = {i: seen.signatures[i].signature for i in signed}
            if unverified:   # the spoiled copy came first and was kept
                got_sig.update({pl.lane: pl.signature for pl in hs.planted
                                if pl.type == 2 and pl.lane in got_sig})
            tallied += sum(1 for i in signed if got_sig[i] != sent.get(i))
        return {
            "heights_whose_block_id_differs_from_the_chain": (wrong_id, 0),
            "heights_whose_app_hash_differs_from_the_model": (wrong_hash, 0),
            "heights_where_the_model_refuses_other_votes_than_planted": (
                unrefused, 0),
            "votes_held_that_the_model_does_not_hold": (foreign, 0),
            "acknowledged_votes_missing_from_the_node_s_sets": (lost, 0),
            "seen_commits_or_vote_sets_without_two_thirds": (wrong_seen, 0),
            "signatures_in_seen_commits_that_were_never_sent": (tallied, 0),
            "planted_votes_whose_spoiled_signature_the_node_held": (
                spoiled_held, 0),
            "planted_votes_acknowledged_before_their_good_copy": (
                len(early), 0),
            "share_of_spoiled_copies_handed_over_that_no_tally_refused": (
                unrefused_share,
                self.run.params["unrefused_spoiled_share_max"]),
            "votes_a_tally_refused_beyond_the_spoiled_copies": (
                max(0, refused - around), 0),
            "lanes_where_reference_verifier_and_record_disagree": (
                tally["disagree"], 0),
            # a micro-batch or an arena flush under the device
            # threshold (40 lanes) is the program's host path by
            # design: the tails of a step's bursts, some hundred lanes
            # a height at most, never a tenth of its ~19,700
            "host_verified_lanes_per_height": (
                self.host_lanes / max(1, hb - ha),
                self.run.params["host_lanes_per_height_max"]),
            "reference_sample_short_of": (
                max(0, self.run.params["reference_sample"]
                    - tally["reference"]) if control is None else 0, 0),
            "_facts": {"heights_checked": hb - ha,
                       "lanes_through_the_reference_verifier":
                           tally["reference"],
                       "spoiled_copies_handed_over_in_the_window": inside,
                       "votes_the_tallies_refused": refused,
                       "planted_votes_whose_held_signature_was_read": sum(
                           1 for k in self.held.signatures if ha < k[0] <= hb),
                       "votes_acknowledged": sum(
                           len(v) for (h, _), v in self.acked.items()
                           if ha < h <= hb)},
        }

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=False, cancel_futures=True)
        if self.node is not None:
            try:
                if self.net is not None:
                    self._on_loop(self.net.stop(self.node.switch), 60)
                self._on_loop(self.node.stop(), timeout=60)
            except Exception as e:  # the result is already decided
                say("node stop failed", error=repr(e))
        if self.loop is not None:
            async def cancel_rest():
                rest = [t for t in asyncio.all_tasks()
                        if t is not asyncio.current_task()]
                for t in rest:
                    t.cancel()
                await asyncio.gather(*rest, return_exceptions=True)

            try:
                self._on_loop(cancel_rest(), timeout=30)
            except Exception as e:  # the thread is a daemon's
                say("loop tasks left", error=repr(e))
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=10)
            if not self._thread.is_alive():
                self.loop.close()
        shutil.rmtree(self.home, ignore_errors=True)
