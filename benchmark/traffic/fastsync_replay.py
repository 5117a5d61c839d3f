"""Traffic: a kvstore chain made from the seed outside the window,
replayed through the fast-sync reactor's own p2p-free engine, in the
reactor's order (`blockchain/reactor.py` `_try_sync`): `WindowPipeline`
verifies a window of `BATCH_WINDOW` commits ahead in one launch while
the previous window's blocks go through `save_block` and
`BlockExecutor.apply_block` (which validates each block's full
LastCommit) into the node's default stores (sqlite, synchronous FULL).

Parameters (the cell's file): `validators`, `blocks` (chain length),
`txs_per_block`, `tx_bytes`. The commit of the last block is planted
bad (one signature among the first 2/3 of the power corrupted), so the
replay must refuse exactly that height; it then starts again from
height 1 into fresh stores, as often as the window lasts.
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import shutil
import time

from benchmark import gen
from benchmark.harness import OUT, BenchFailure, say
from benchmark.reference import canonical
from benchmark.reference.kvstore_model import KVStoreModel

CHAIN_ID = "bench-fastsync"
GENESIS_TIME = 1_753_928_000_000_000_000


class Driver:
    CONTROLS = ("skips_commit_check", "stale_app_state")

    def __init__(self, run):
        self.run = run
        self.n = run.params["validators"]
        self.dir = os.path.join(OUT, "sync-" + run.cell.name)
        self.passes: list[dict] = []
        self._pass_no = 0

    # ------------------------------------------------------- the chain

    def setup(self) -> None:
        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.kvstore import KVStoreApp
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import update_state
        from tendermint_tpu.types.block import (
            BlockID, BlockIDFlag, Commit, CommitSig)
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

        run, n, p = self.run, self.n, self.run.params
        t0 = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        _, self.pubs = gen.validator_order(run.seed, n)
        self.gdoc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=GENESIS_TIME,
            validators=[GenesisValidator(Ed25519PubKey(pk), 1)
                        for pk in self.pubs])
        self.gdoc.validate_and_complete()
        state = make_genesis_state(self.gdoc)
        if [v.pub_key.bytes() for v in state.validators.validators] \
                != self.pubs:
            raise BenchFailure("the program orders the validator set "
                               "differently from the reference")
        addrs = [canonical.address(pk) for pk in self.pubs]
        # the tables build on the device while the chain is signed
        warm = state.validators.warm_device_tables()
        app = KVStoreApp()
        model = KVStoreModel()
        self.blocks, self.expected = [], []
        n_blocks = p["blocks"]
        bad_height = n_blocks - 1   # its commit rides in the last block
        rng = run.rng("chain")
        bad_lane = int(rng.integers(n // 2))
        last_commit = None
        # block h+1 holds the commit of block h, so the chain is made
        # in order; a commit's signatures are split over the pool
        pool = gen.make_pool()
        step = -(-n // pool._max_workers)
        for h in range(1, n_blocks + 1):
            txs = [self._tx(h, k) for k in range(p["txs_per_block"])]
            if h == 1:
                when = state.last_block_time
            else:   # BFT time: the median vote time (equal powers)
                when = sorted(cs.timestamp for cs in
                              last_commit.signatures)[(n + 1) // 2 - 1]
            block = state.make_block(
                h, txs, last_commit, [],
                state.validators.get_proposer().address, when)
            parts = block.make_part_set()
            bid = BlockID(block.hash(), parts.header())
            self.blocks.append(block)
            app.begin_block(abci_t.RequestBeginBlock())
            delivered = [app.deliver_tx(abci_t.RequestDeliverTx(tx))
                         for tx in txs]
            end = app.end_block(abci_t.RequestEndBlock(h))
            state = update_state(state, bid, block, {
                "deliver_txs": delivered, "end_block": end}, [])
            state.app_hash = app.commit(abci_t.RequestCommit()).data
            for tx in txs:
                model.deliver(tx)
            self.expected.append(model.app_hash())
            psh = parts.header()
            pre, suf = canonical.vote_sign_parts(
                CHAIN_ID, h, 0, block.hash(), psh.total, psh.hash)
            times = [when + 1_000_000_000 + i * 1_000 for i in range(n)]
            futs = [pool.submit(gen.sign_slice, run.seed, n, lo,
                                min(n, lo + step), pre, suf,
                                times[lo:lo + step])
                    for lo in range(0, n, step)]
            raw = b"".join(f.result() for f in futs)
            sigs = [raw[64 * i:64 * i + 64] for i in range(n)]
            if h == bad_height:
                sigs[bad_lane] = gen.corrupt(sigs[bad_lane], "s_bit")
            last_commit = Commit(h, 0, bid, [
                CommitSig(BlockIDFlag.COMMIT, addrs[i], times[i], sigs[i])
                for i in range(n)])
        pool.shutdown(wait=True)
        self.bad_height = bad_height
        if warm is not None:
            warm.join()
        say("chain ready", validators=n, blocks=n_blocks,
            refused_height=bad_height,
            seconds=round(time.perf_counter() - t0, 3))

    def _tx(self, h: int, k: int) -> bytes:
        tag = hashlib.sha256(
            f"bench/sync/{self.run.seed}/{h}/{k}".encode()).hexdigest()
        return (f"k{h:x}.{k:x}=".encode() + tag.encode() * 2)[
            :self.run.params["tx_bytes"]]

    # ------------------------------------------------------ one replay

    async def _replay(self, deadline: float | None) -> dict:
        """Blocks 1.. through verify-ahead windows into fresh stores,
        until the chain refuses a block, runs out or `deadline`."""
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

        run = self.run
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
        out = {"applied": 0, "sigs": 0, "refused_at": None,
               "t0": time.perf_counter()}

        def peek(k):
            return self.blocks[pos:pos + k]

        def late():
            return deadline is not None and time.perf_counter() >= deadline

        try:
            while out["refused_at"] is None and not late():
                window = peek(BATCH_WINDOW + 1)
                if len(window) < 2:
                    break
                vals = state.validators
                with run.span("await_window"):
                    items, parts_list, results = await pipeline.verdicts(
                        vals, CHAIN_ID, window)
                pipeline.start_ahead(vals, CHAIN_ID, peek, len(window))
                for i, err in enumerate(results):
                    if err is not None:
                        out["refused_at"] = items[i][1]
                        break
                    if late():
                        break
                    with run.span("apply_block"):
                        block_store.save_block(window[i], parts_list[i],
                                               window[i + 1].last_commit)
                        state, _ = await executor.apply_block(
                            state, items[i][0], window[i])
                    pos += 1
                    out["applied"] += 1
                    out["sigs"] += sum(
                        1 for cs in window[i + 1].last_commit.signatures
                        if not cs.is_absent())
                run.ledger.drain()
        finally:
            pf = pipeline._prefetch
            if pf is not None:  # let an in-flight window land
                await asyncio.wait([pf[1]])
            await client.stop()
            for d in dbs:
                d.close()
        out["t1"] = time.perf_counter()
        out["height"] = state.last_block_height
        out["app_hash"] = state.app_hash
        out["store_height"] = block_store.height
        return out

    def warm(self) -> None:
        """The two shapes a replay launches: the 16-block window and the
        per-block LastCommit check. They compile (or load) side by
        side: a thread verifies block 1's commit while a short replay
        goes through its first window."""
        import threading

        from tendermint_tpu.blockchain.verify_ahead import BATCH_WINDOW
        from tendermint_tpu.state import make_genesis_state

        vals = make_genesis_state(self.gdoc).validators
        second = self.blocks[1]
        t0 = time.perf_counter()
        side = threading.Thread(target=vals.verify_commit, args=(
            CHAIN_ID, second.header.last_block_id, 1, second.last_commit))
        side.start()
        keep, self.blocks = self.blocks, self.blocks[:2 * BATCH_WINDOW + 2]
        try:
            out = asyncio.run(self._replay(None))
        finally:
            self.blocks = keep
            side.join()
        say("warm replay", applied=out["applied"],
            seconds=round(time.perf_counter() - t0, 3))

    def measure(self, seconds: float) -> dict:
        run = self.run
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            self.passes.append(asyncio.run(self._replay(deadline)))
        t1 = time.perf_counter()
        run.ledger.drain()
        applied = sum(p["applied"] for p in self.passes)
        sigs = sum(p["sigs"] for p in self.passes)
        run.counters["blocks_applied"] = applied
        say("window", passes=len(self.passes), blocks_applied=applied,
            blocks_per_s=applied / (t1 - t0), seconds=t1 - t0)
        return {"attempted": applied, "failed": 0,
                "metrics": {"sigs_per_s": sigs / (t1 - t0)}}

    # ---------------------------------------------------------- check

    def check(self, control: str | None = None) -> dict:
        """Every replay of the window against what the generator
        recorded with the dict model."""
        wrong_height = wrong_hash = wrong_refusal = 0
        for p in self.passes:
            height, app_hash, refused = (p["height"], p["app_hash"],
                                         p["refused_at"])
            if control == "skips_commit_check":
                # a sync that applies blocks without verifying their
                # commits walks over the planted one
                refused, height = None, len(self.blocks) - 1
                app_hash = self.expected[height - 1]
            if control == "stale_app_state":
                # an app one block behind the height it reports
                app_hash = self.expected[max(0, height - 2)]
            cut_short = refused is None and height < self.bad_height - 1
            if not cut_short and refused != self.bad_height:
                wrong_refusal += 1
            if refused is not None and height != refused - 1:
                wrong_height += 1
            if p["store_height"] != p["height"]:
                wrong_height += 1
            if height and app_hash != self.expected[height - 1]:
                wrong_hash += 1
        return {
            "replays_refusing_another_height_than_planted": (
                wrong_refusal, 0),
            "replays_ending_at_another_height": (wrong_height, 0),
            "replays_whose_app_hash_differs_from_the_model": (wrong_hash, 0),
            "_facts": {"replays": len(self.passes),
                       "planted_refusal_height": self.bad_height,
                       "ended_at": [p["height"] for p in self.passes]},
        }

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
