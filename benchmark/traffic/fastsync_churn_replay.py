"""Traffic: a kvstore chain whose validator set moves, made from the
seed outside the window and replayed through the program's own
p2p-free fast-sync loop (`blockchain/verify_ahead.py` `sync_window`,
the function `BlockchainReactor._try_sync` calls): a window of
`BATCH_WINDOW` commits verified ahead in one launch, `save_block` and
`BlockExecutor.apply_block` a block, and a stop at the first block
after which the set in force moved.

Parameters (the cell's file), beside the still-set driver's:
`update_every_blocks` (a block of `val:` txs every so many heights,
from that height on), alternating a membership change
(`membership_swap` validators leave, as many new keys join with the
leavers' powers) and a re-weighting (`reweighted` validators get a new
power from `power_band`, "lo-hi"). WHICH validators is the seed's; how
many and when is the file's, so every seed does the same work.

One commit is planted bad: that of height `blocks` - 1, one signature
of a validator that joined by update. The replay must apply every block
below it, refuse exactly that height and name the signature's index in
the order in force; it then starts again from height 1 into fresh
stores, as often as the window lasts. Every replay is thus the same
changes, ten blocks apart, to the chain's end.

The chain's tail adds window shapes of its own (the last windows,
verified now or ahead, hold fewer commits than `BATCH_WINDOW`): `_plan`
walks a replay's launches and names them, `_check_shapes` holds each to
one lane bucket over every set, and the warm replay is a whole replay,
so set-up compiles each of them.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import shutil
import threading
import time

from benchmark import gen
from benchmark.harness import BenchFailure, say
from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref
from benchmark.reference import valset_model as vm
from benchmark.traffic import fastsync_replay
from benchmark.traffic.fastsync_replay import CHAIN_ID, GENESIS_TIME

SAMPLED_LANES = 512
NAMED = "invalid signature(s) at index(es) ["


# -------------------------------------------------------------- the pool

_KEYS: dict = {}   # a pool worker's private keys, by (seed, purpose, i)


def sign_ids(seed: int, ids: list[tuple[str, int]], pre: bytes, suf: bytes,
             times: list[int]) -> bytes:
    """Pool worker: the precommit signatures of the validators `ids`
    (key purpose and number, `gen.private_key`) over the reference's
    sign bytes at times[i], joined."""
    out = []
    for (purpose, i), t in zip(ids, times):
        key = _KEYS.get((seed, purpose, i))
        if key is None:
            key = _KEYS[(seed, purpose, i)] = gen.private_key(
                seed, purpose, i)
        out.append(key.sign(canonical.with_timestamp(pre, suf, t)))
    return b"".join(out)


def lane_bucket(lanes: int) -> int:
    """The launch shape `lanes` fall into: powers of two from 128 to
    1,024, then multiples of 1,024 (crypto/tpu/expanded.py `_bucket`,
    restated: a window that left its bucket would compile)."""
    if lanes <= 1024:
        return max(128, 1 << (lanes - 1).bit_length())
    return -(-lanes // 1024) * 1024


class Driver(fastsync_replay.Driver):
    CONTROLS = ("skips_commit_check", "stale_app_state", "ignores_updates",
                "stale_key_order")

    def __init__(self, run):
        super().__init__(run)
        try:   # a program without the shared loop cannot run this cell
            from tendermint_tpu.blockchain.verify_ahead import sync_window
        except ImportError as e:
            raise BenchFailure(f"the program has no sync_window: {e}")
        self.sync_window = sync_window

    # ------------------------------------------------------- the chain

    def _plan(self, window: int) -> None:
        """The chain's length, the planted height, the update heights,
        and a walk through every window a replay launches, verified now
        or ahead: the commits each holds."""
        p = self.run.params
        every = p["update_every_blocks"]
        self.length = p["blocks"]
        self.bad = self.length - 1    # block `length` carries its commit
        self.end = self.bad - 1       # the last height a replay applies
        # every update whose set comes into force inside a replay
        self.updates = list(range(every, self.end - 1, every))
        if len(self.updates) < 2:
            raise BenchFailure(
                f"{self.length} blocks hold no update of each kind")
        pos, self.window_commits = 0, set()
        while pos < self.end:
            verifiable = min(window, self.length - pos - 1)
            ahead = min(window + 1, self.length - pos - verifiable) - 1
            self.window_commits.add(verifiable)
            if ahead >= 1:
                self.window_commits.add(ahead)
            for h in range(pos + 1, pos + verifiable + 1):
                if h > self.end:
                    break              # refused
                pos = h                # block h applied
                if h - 1 in self.updates:
                    break              # the set moved: the window is cut

    def setup(self) -> None:
        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
        from tendermint_tpu.blockchain.verify_ahead import BATCH_WINDOW
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import (
            update_state, validator_updates_from_abci)
        from tendermint_tpu.types.block import (
            BlockID, BlockIDFlag, Commit, CommitSig)
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

        run, n, p = self.run, self.n, self.run.params
        t0 = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        self._plan(BATCH_WINDOW)
        lo, hi = (int(x) for x in p["power_band"].split("-"))
        rng = run.rng("churn")
        self.ids = {}     # public key -> (purpose, number) of its key
        for i in range(n):
            self.ids[gen.public_bytes(gen.private_key(
                run.seed, "val", i))] = ("val", i)
        genesis = dict(zip(self.ids, (int(x) for x in
                                      rng.integers(lo, hi + 1, n))))
        self.model = vm.ValsetModel(genesis)
        self.idle = vm.ValsetModel(genesis, apply_updates=False)
        self.gdoc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=GENESIS_TIME,
            validators=[GenesisValidator(Ed25519PubKey(pk), power)
                        for pk, power in genesis.items()])
        self.gdoc.validate_and_complete()
        state = make_genesis_state(self.gdoc)
        if self._set_of(state.validators) != self.model.in_force(1):
            raise BenchFailure("the program orders the genesis set "
                               "differently from the reference")
        # the tables build on the device while the chain is signed
        warm = state.validators.warm_device_tables()
        app = PersistentKVStoreApp()
        app.init_chain(abci_t.RequestInitChain(validators=[
            abci_t.ValidatorUpdate("ed25519", pk, power)
            for pk, power in genesis.items()]))
        kv = vm.PersistentKVStoreModel()
        self.blocks, self.expected = [], []
        self.after_change = {}   # height -> (pre, suf, times, sigs)
        current = dict(genesis)  # the set the next update changes
        joined = 0
        last_commit = None
        pool = gen.make_pool()
        step = -(-n // pool._max_workers)
        for h in range(1, self.length + 1):
            txs = [self._tx(h, k) for k in range(p["txs_per_block"])]
            if h in self.updates:
                if self.updates.index(h) % 2 == 0:
                    change = self._membership(rng, current, joined)
                    joined += p["membership_swap"]
                else:
                    change = self._reweighting(rng, current, lo, hi)
                for pk, power in change:
                    if power:
                        current[pk] = power
                    else:
                        del current[pk]
                txs += [vm.val_tx(pk, power) for pk, power in change]
            if h == 1:
                when = state.last_block_time
            else:   # BFT time: the weighted median of the votes
                when = vm.weighted_median(list(zip(
                    times, (pw for _, pw in signers))))
            proposer = state.validators.get_proposer().address
            block = state.make_block(h, txs, last_commit, [], proposer,
                                     when)
            parts = block.make_part_set()
            bid = BlockID(block.hash(), parts.header())
            self.blocks.append(block)
            app.begin_block(abci_t.RequestBeginBlock())
            delivered = [app.deliver_tx(abci_t.RequestDeliverTx(tx))
                         for tx in txs]
            end = app.end_block(abci_t.RequestEndBlock(h))
            state = update_state(
                state, bid, block,
                {"deliver_txs": delivered, "end_block": end},
                validator_updates_from_abci(end.validator_updates))
            state.app_hash = app.commit(abci_t.RequestCommit()).data
            for tx in txs:
                kv.deliver(tx)
            self.expected.append(kv.app_hash())
            self.model.deliver_block(h, txs)
            self.idle.deliver_block(h, txs)
            signers = self.model.in_force(h)
            if h - 2 in self.updates and \
                    [k for k, _ in signers] == \
                    [k for k, _ in self.model.in_force(h - 1)]:
                raise BenchFailure(f"the update of block {h - 2} did "
                                   f"not move the order")
            psh = parts.header()
            pre, suf = canonical.vote_sign_parts(
                CHAIN_ID, h, 0, block.hash(), psh.total, psh.hash)
            times = [when + 1_000_000_000 + i * 1_000 for i in range(n)]
            ids = [self.ids[pk] for pk, _ in signers]
            futs = [pool.submit(sign_ids, run.seed, ids[at:at + step],
                                pre, suf, times[at:at + step])
                    for at in range(0, n, step)]
            raw = b"".join(f.result() for f in futs)
            sigs = [raw[64 * i:64 * i + 64] for i in range(n)]
            if h - 2 in self.updates:
                self.after_change[h] = (pre, suf, times, list(sigs))
            if h == self.bad:
                lane = self._joiner_lane(signers)
                sigs[lane] = gen.corrupt(sigs[lane], "s_bit")
                # (index, pre, suf, time, sig) of the planted signature
                self.planted = (lane, pre, suf, times[lane], sigs[lane])
            last_commit = Commit(h, 0, bid, [
                CommitSig(BlockIDFlag.COMMIT, canonical.address(pk),
                          times[i], sigs[i])
                for i, (pk, _) in enumerate(signers)])
        pool.shutdown(wait=True)
        self._check_shapes()
        if warm is not None:
            warm.join()
        self.refusal = (self.bad, [self.planted[0]])
        say("chain ready", validators=n, blocks=self.length,
            updates=self.updates, planted=self.refusal,
            replay_ends_at=self.end, shapes=self.shapes,
            seconds=round(time.perf_counter() - t0, 3))

    @staticmethod
    def _set_of(vals) -> list[tuple[bytes, int]]:
        return [(v.pub_key.bytes(), v.voting_power)
                for v in vals.validators]

    def _membership(self, rng, current: dict, joined: int) -> list:
        """`membership_swap` validators leave (the first drawn from the
        top third of the order, so that a joiner lands among the lanes
        a light check reads) and as many new keys take their powers."""
        k = self.run.params["membership_swap"]
        order = [pk for pk, _ in vm.ordered(current)]
        first = order[int(rng.integers(len(order) // 3))]
        rest = [pk for pk in order if pk != first]
        leavers = [first] + [rest[i] for i in rng.choice(
            len(rest), k - 1, replace=False)]
        change = [(pk, 0) for pk in leavers]
        for j, pk in enumerate(leavers):
            key = gen.public_bytes(gen.private_key(
                self.run.seed, "join", joined + j))
            self.ids[key] = ("join", joined + j)
            change.append((key, current[pk]))
        return change

    def _reweighting(self, rng, current: dict, lo: int, hi: int) -> list:
        """`reweighted` validators get another power from the band."""
        keys = sorted(current)
        change = []
        for i in rng.choice(len(keys), self.run.params["reweighted"],
                            replace=False):
            power = int(rng.integers(lo, hi))   # hi - lo other values
            change.append((keys[i], power + (power >= current[keys[i]])))
        return change

    def _joiner_lane(self, signers) -> int:
        """The index, in the order in force, of a validator that
        joined by update and stands in the first half."""
        for i, (pk, _) in enumerate(signers[:self.n // 2]):
            if self.ids[pk][0] == "join":
                return i
        raise BenchFailure("no joined validator in the first half of "
                           "the set that signs the planted commit")

    def _check_shapes(self) -> None:
        """Over every set of the chain, each window a replay launches
        falls into ONE launch shape, and one commit's light check into
        the shape of a block's full LastCommit check."""
        light = {vm.light_lanes(self.model.in_force(h))
                 for h in range(1, self.length + 1)}
        shapes = {c: {lane_bucket(c * x) for x in light}
                  for c in sorted(self.window_commits | {1})}
        shapes[1].add(lane_bucket(self.n))
        if any(len(b) != 1 for b in shapes.values()):
            raise BenchFailure(
                f"light checks of {min(light)}-{max(light)} lanes give "
                f"windows of {sorted(shapes)} commits the launch shapes "
                f"{[sorted(b) for b in shapes.values()]}: more than one "
                f"a window")
        self.shapes = {"light_lanes": (min(light), max(light)),
                       "lanes_by_commits": {c: b.pop()
                                            for c, b in shapes.items()}}

    # ------------------------------------------------------ one replay

    async def _replay(self, deadline: float | None) -> dict:
        """Blocks 1.. through the program's sync loop into fresh
        stores, until a refusal (or the chain runs out), or the first
        window's end past `deadline`."""
        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.client import LocalClient
        from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
        from tendermint_tpu.blockchain.verify_ahead import (
            BATCH_WINDOW, WindowPipeline)
        from tendermint_tpu.config import Config
        from tendermint_tpu.libs.db import SqliteDB
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.store import Store
        from tendermint_tpu.store import BlockStore
        from tendermint_tpu.types.validator_set import VerificationError

        run = self.run
        t_begin = time.perf_counter()
        self._pass_no += 1
        shutil.rmtree(self.dir, ignore_errors=True)
        sync = Config().base.db_synchronous   # the node's default

        def db(name):
            return SqliteDB(os.path.join(self.dir, f"p{self._pass_no}",
                                         name + ".sqlite"), synchronous=sync)

        dbs = [db("state"), db("blockstore"), db("app")]
        state = make_genesis_state(self.gdoc)
        store = Store(dbs[0])
        store.save(state)
        block_store = BlockStore(dbs[1])
        app = PersistentKVStoreApp(dbs[2])
        app.init_chain(abci_t.RequestInitChain(validators=[
            abci_t.ValidatorUpdate("ed25519", v.pub_key.bytes(),
                                   v.voting_power)
            for v in state.validators.validators]))
        client = LocalClient(app)
        await client.start()
        executor = BlockExecutor(store, client)
        pipeline = WindowPipeline()
        pos = 0
        out = {"applied": 0, "refusals": [], "sets": {}, "updates": 0,
               "ended": "chain"}
        clock = contextlib.ExitStack()   # the harness span that is open
        # where an untraced run's time went: [seconds, longest] waiting
        # for a window's verdicts and applying a block
        waits, applies = [0.0, 0.0], [0.0, 0.0]

        def lap(into):
            nonlocal since
            now = time.perf_counter()
            into[0] += now - since
            into[1] = max(into[1], now - since)
            since = now

        def peek(k):
            return self.blocks[pos:pos + k]

        def before_block(block):
            clock.close()
            lap(waits)
            clock.enter_context(run.span("apply_block"))

        def after_block(new_state, block):
            nonlocal pos
            clock.close()
            lap(applies)
            h = block.header.height
            pos += 1
            out["applied"] += 1
            if h in self.updates:
                out["updates"] += 1
            if h - 1 in self.updates:   # in force from the next height
                out["sets"][h + 1] = new_state.validators

        since = t_loop = time.perf_counter()
        try:
            while True:
                if deadline is not None and time.perf_counter() >= deadline:
                    out["ended"] = "cut"
                    break
                window = peek(BATCH_WINDOW + 1)
                if len(window) < 2:
                    break
                clock.enter_context(run.span("await_window"))
                state, _, refused = await self.sync_window(
                    pipeline, state, window, peek, block_store, executor,
                    before_block, after_block)
                clock.close()
                run.ledger.drain()
                if refused is None:
                    continue
                # what an operator asks of a refused commit: which
                # signature, by its index in the set in force
                commit = window[refused.index + 1].last_commit
                named = None
                try:
                    state.validators.verify_commit_light(
                        CHAIN_ID, commit.block_id, refused.height, commit)
                except VerificationError as e:
                    if str(e).startswith(NAMED):
                        named = [int(x) for x in
                                 str(e)[len(NAMED):-1].split(",")]
                out["refusals"].append((refused.height, named))
                out["ended"] = "refused"
                break
            out["loop_end"] = time.perf_counter()
            out["seconds"] = {
                "all": round(out["loop_end"] - t_begin, 3),
                "fresh_stores": round(t_loop - t_begin, 3),
                "await_windows": [round(x, 3) for x in waits],
                "apply_blocks": [round(x, 3) for x in applies]}
        finally:
            clock.close()
            pf = pipeline._prefetch
            if pf is not None:  # let an in-flight window land
                await asyncio.wait([pf[1]])
            await client.stop()
            for d in dbs:
                d.close()
        out["height"] = state.last_block_height
        out["app_hash"] = state.app_hash
        out["store_height"] = block_store.height
        return out

    def warm(self) -> None:
        """Every shape a replay launches: each window of `_plan`, one
        commit (a block's LastCommit check, the refused commit's index)
        and the table builder's. A whole replay, through every update
        and the tail; block 1's commit is verified beside it so that
        the first two verify shapes compile side by side."""
        from tendermint_tpu.state import make_genesis_state

        vals = make_genesis_state(self.gdoc).validators
        second = self.blocks[1]
        t0 = time.perf_counter()
        side = threading.Thread(target=vals.verify_commit, args=(
            CHAIN_ID, second.header.last_block_id, 1, second.last_commit))
        side.start()
        try:
            out = asyncio.run(self._replay(None))
        finally:
            side.join()
            self._join_builds()
        if (out["applied"], out["refusals"]) != (self.end, [self.refusal]):
            raise BenchFailure(
                f"the warm replay applied {out['applied']} blocks and "
                f"refused {out['refusals']}: not {self.end} and "
                f"{self.refusal}")
        say("warm replay", applied=out["applied"],
            refusals=out["refusals"],
            seconds=round(time.perf_counter() - t0, 3))

    @staticmethod
    def _join_builds() -> None:
        """A table build the last block applied set off, and no window
        waited for, ends before anything else is timed or closed."""
        for t in threading.enumerate():
            if t.name == "expanded-warm":
                t.join()

    def measure(self, seconds: float) -> dict:
        """Replays until the first window's end past `seconds`: the
        rate is over the time to that end (a window waits whole
        seconds for a table build, and nothing of it is left out)."""
        run = self.run
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            self.passes.append(asyncio.run(self._replay(deadline)))
        t1 = self.passes[-1]["loop_end"]
        self._join_builds()
        run.ledger.drain()
        applied = sum(p["applied"] for p in self.passes)
        sigs = applied * self.n   # every validator signs every commit
        run.counters["blocks_applied"] = applied
        run.counters["valset_updates"] = sum(
            p["updates"] for p in self.passes)
        say("window", passes=len(self.passes), blocks_applied=applied,
            updates_applied=run.counters["valset_updates"],
            blocks_per_s=applied / (t1 - t0), seconds=t1 - t0,
            by_pass=[dict(p["seconds"], applied=p["applied"])
                     for p in self.passes])
        return {"attempted": applied, "failed": 0,
                "metrics": {"sigs_per_s": sigs / (t1 - t0)}}

    # ---------------------------------------------------------- check

    def check(self, control: str | None = None) -> dict:
        """Every replay of the window against the models: the dict
        model's app hash, the moving set, the planted commit's height
        and index, and lanes of the first commit under each new set
        against the reference verifier."""
        model = self.idle if control == "ignores_updates" else self.model
        wrong_refusal = wrong_height = wrong_hash = wrong_set = 0
        reached = 0
        for p in self.passes:
            height, app_hash, refusals = (p["height"], p["app_hash"],
                                          p["refusals"])
            if control == "skips_commit_check":
                # a sync that applies blocks without verifying their
                # commits takes the planted one with the others
                refusals = []
            if control == "stale_app_state":
                # an app one block behind the height it reports
                app_hash = self.expected[max(0, height - 2)]
            # the planted commit is met by the call that applies the
            # block below it
            if refusals != [self.refusal] * (height >= self.end):
                wrong_refusal += 1
            if p["store_height"] != height or (
                    p["ended"] != "cut" and height != self.end):
                wrong_height += 1
            if height and app_hash != self.expected[height - 1]:
                wrong_hash += 1
            for h, vals in p["sets"].items():
                got, want = self._set_of(vals), model.in_force(h)
                if got != want or vals.hash() != vm.validators_hash(want):
                    wrong_set += 1
            reached = max(reached, height)
        lanes, wrong_lanes = self._sampled_lanes(
            reached, stale=control == "stale_key_order")
        return {
            "replays_refusing_otherwise_than_the_planted_commit": (
                wrong_refusal, 0),
            "replays_ending_at_another_height": (wrong_height, 0),
            "replays_whose_app_hash_differs_from_the_model": (wrong_hash, 0),
            "sets_in_force_that_differ_from_the_model": (wrong_set, 0),
            "lanes_the_reference_decides_otherwise": (wrong_lanes, 0),
            "sampled_lanes_short_of_the_floor": (
                max(0, min(SAMPLED_LANES, self._lanes_due(reached))
                    - lanes), 0),
            "_facts": {"replays": len(self.passes),
                       "planted": self.refusal,
                       "refusals": [p["refusals"] for p in self.passes],
                       "ended_at": [p["height"] for p in self.passes],
                       "sets_compared": sum(len(p["sets"])
                                            for p in self.passes),
                       "lanes_sampled": lanes},
        }

    def _changes_applied(self, reached: int) -> list[int]:
        """Heights of the first commit under each new set that some
        replay verified twice: in its window and, every lane, as the
        next block's LastCommit."""
        return [h for h in sorted(self.after_change) if h + 1 <= reached]

    def _lanes_due(self, reached: int) -> int:
        return self.n * len(self._changes_applied(reached))

    def _sampled_lanes(self, reached: int, stale: bool) -> tuple[int, int]:
        """(lanes checked, lanes the reference decides otherwise): the
        program accepted every lane of the first commit under each new
        set, and refused the planted signature; the reference verifies
        each under the model's key for that index (`stale`: under the
        key the previous set held there). Lanes whose key moved come
        first."""
        heights = self._changes_applied(reached)
        if not heights:
            return 0, 0
        rng = self.run.rng("lanes")
        share = -(-SAMPLED_LANES // len(heights))
        checked = wrong = 0
        for h in heights:
            pre, suf, times, sigs = self.after_change[h]
            now = [pk for pk, _ in self.model.in_force(h)]
            was = [pk for pk, _ in self.model.in_force(h - 1)]
            moved = [i for i in range(self.n) if now[i] != was[i]]
            still = [i for i in range(self.n) if now[i] == was[i]]
            lanes = [moved[i] for i in rng.permutation(len(moved))]
            lanes += [still[i] for i in rng.permutation(len(still))]
            for i in lanes[:share]:
                key = was[i] if stale else now[i]
                wrong += not ref.verify(key, canonical.with_timestamp(
                    pre, suf, times[i]), sigs[i])
                checked += 1
        if reached >= self.end:   # the planted commit was refused
            lane, pre, suf, when, sig = self.planted
            key = self.model.in_force(self.bad)[lane][0]
            wrong += ref.verify(key, canonical.with_timestamp(
                pre, suf, when), sig)
            checked += 1
        return checked, wrong
